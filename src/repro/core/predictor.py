"""Straggler Prediction module (paper Fig. 1 / Fig. 4): Encoder-LSTM -> Pareto.

Ties together feature extraction, the Encoder-LSTM network and the Pareto
expected-straggler computation, and owns network training (MSE against
MLE-fitted (alpha, beta) targets — paper §4.4).

Inference is shape-disciplined: ``predict_features`` pads the job batch to
a power-of-two bucket before entering the jitted network, so a sweep cell
compiles **once per bucket size**, never once per active-job count (the
silent-retrace failure mode: every new job count is a new batch shape and
a full XLA retrace).  ``buckets_used`` records the bucket set for
retrace-accounting tests and benchmarks.

The per-interval hot path is the **fused step** (``_fused_step``): the
M_H history lives in a device-resident ring buffer that is rolled
*inside* a single donated-buffer jitted program which also assembles the
feature batch on device, runs the Encoder-LSTM and reduces straight to
E_S (the Pareto tail included).  A warm interval runs that one program:
the packed staging vector (new M_H row + M_T batch + q + scalars)
enters as the launch's own argument, with no separate upload program,
and the host reads back one (bucket,) E_S vector — the full history
matrix never crosses the host/device boundary again, and the ~10 small
eager dispatches of the historical path collapse into one.

Determinism is **tiered** (see README "Performance"):

  * Tier-0 (bitwise): the engine, sweep serial == parallel, and the
    golden determinism fixture.  The *unfused* path here
    (``predict_features`` -> ``predict_sequence`` -> ``_pareto_tail``)
    is the bitwise reference the fixture was blessed against and is
    never restructured.
  * Tier-1 (tolerance-bounded): the fused step and the serving batch
    path.  They restructure the emission for speed — encoder hoisted
    out of the scan with the shared host block encoded once per step
    (``net.encoder_hoisted``), the scan unrolled by 2, the Pareto tail
    fused into the same program, exact-shape batches — and
    agree with the reference within the documented bound in
    ``tests/tolerance.py`` at every shape (tested by shape sweep).
    Every Tier-1 path is still fully deterministic run-to-run on one
    machine; only cross-path bitwise equality is relaxed.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import encoder_lstm as net
from repro.core import features, pareto
from repro.trace import span


class Prediction(NamedTuple):
    alpha: jax.Array      # (...,)
    beta: jax.Array       # (...,)
    threshold: jax.Array  # K  (...,)
    e_s: jax.Array        # expected straggler count (...,)


def bucket_size(n: int) -> int:
    """Smallest power of two >= n (the jit batch-shape bucket)."""
    return max(1 << (int(n) - 1).bit_length(), 1) if n else 1


# uploads outside the warm interval (ring rebuilds, catch-up rows, tenant
# slot rebuilds) ride the pjit fast path (see StragglerPredictor._stage)
_stage_put = jax.jit(lambda x: x)

#: the fused programs' batch policy (``StragglerPredictor.batch_size``):
#: a count whose power-of-two bucket would waste more than this share of
#: its rows runs at its exact width instead (0.44 of a 16-bucket for a
#: 9-job batch) ...
PAD_WASTE_LIMIT = 0.25
#: ... for at most this many distinct exact shapes per predictor; once
#: spent, new counts pad again, so a long-lived process compiles at most
#: ``len(buckets) + EXACT_SHAPES_LIMIT`` batch shapes.
EXACT_SHAPES_LIMIT = 8


# --------------------------- fused interval step ---------------------------
#
# Packed staging layout (one float32 vector, one host->device transfer per
# interval, as the launch's own argument):
# [k, beta_scale, new_mh_row(host_dim), q(nb), m_t(nb*task_dim)].
_N_SCALARS = 2


@functools.partial(jax.jit, donate_argnums=(1,),
                   static_argnames=("nb", "task_dim", "use_pallas",
                                    "per_task", "unroll"))
def _fused_step(params, ring, packed, *, nb: int, task_dim: int,
                use_pallas: bool | str = False, per_task: bool = False,
                unroll: int = 1):
    """One whole START decision step as a single device program (Tier-1).

    Rolls the donated M_H ring buffer by the staged row, then runs the
    restructured emission the tiered determinism contract unblocked:

      * the encoder is hoisted out of the recurrent scan and the shared
        host block is encoded once per step instead of once per (step,
        job) (``net.encoder_hoisted`` — the task block's constant-EMA
        is dropped there too);
      * the LSTM scan unrolls by the static ``unroll`` factor
        (:meth:`StragglerPredictor._unroll`);
      * the Pareto tail — and with ``per_task=True`` the per-task score
        decomposition — is fused INTO this program, so a warm interval
        is exactly one dispatch and one readback (historically the tail
        was a second dispatch, split out to preserve bitwiseness).

    Each restructuring shifts float rounding by ulps at some shapes, so
    the program agrees with the unfused reference within the documented
    Tier-1 bound (tests/tolerance.py) rather than bitwise; it is still
    fully deterministic for a fixed (shape, unroll, platform).

    Returns ``(new_ring, e_s)`` — or ``(new_ring, packed_out)`` with
    ``packed_out = [E_S | per-task scores]`` of shape
    ``(nb, 1 + max_tasks)`` when ``per_task`` (same packing as
    :func:`_pareto_tail_per_task`).
    """
    host_dim = ring.shape[1]
    k = packed[0]
    beta_scale = packed[1]
    row = packed[_N_SCALARS:_N_SCALARS + host_dim]
    q = packed[_N_SCALARS + host_dim:_N_SCALARS + host_dim + nb]
    mt = packed[_N_SCALARS + host_dim + nb:].reshape(nb, task_dim)
    ring2 = jnp.concatenate([ring[1:], row[None]], axis=0)
    mh_ema = net.ema_smooth(ring2)                        # (T, host_dim)
    lam = net.encoder_hoisted(params, mh_ema, mt)         # (T, nb, E)
    return ring2, _decode_tail(params, lam, k, beta_scale, q, mt, nb=nb,
                               task_dim=task_dim, use_pallas=use_pallas,
                               per_task=per_task, unroll=unroll)


def _decode_tail(params, lam, k, beta_scale, q, mt, *, nb, task_dim,
                 use_pallas, per_task, unroll):
    """The fused programs' common end: the LSTM scan over the encodings
    ``lam``, the Pareto tail to E_S, and with ``per_task`` the per-task
    scores (packed ``[E_S | scores]``, as :func:`_pareto_tail_per_task`
    packs them)."""
    ab = net.decode_sequence(params, lam, unroll=unroll,
                             use_pallas=use_pallas)
    alpha = ab[..., 0]
    beta = ab[..., 1] * beta_scale
    thr = k * (alpha * beta / (alpha - 1.0))
    kk = thr / beta
    e_s = q * kk ** (-alpha)
    if per_task:
        max_tasks = task_dim // features.TASK_FEATURES
        mt3 = mt.reshape(nb, max_tasks, features.TASK_FEATURES)
        demand = mt3[..., :4].sum(axis=-1)              # (nb, max_tasks)
        total = demand.sum(axis=-1, keepdims=True)
        real = jnp.arange(max_tasks)[None, :] < q[:, None]
        uniform = real / jnp.maximum(q, 1.0)[:, None]
        share = jnp.where(total > 0.0,
                          demand / jnp.where(total > 0.0, total, 1.0),
                          uniform)
        scores = e_s[:, None] * share
        return jnp.concatenate([e_s[:, None], scores], axis=1)
    return e_s


# ----------------------- tenant-batched interval step -----------------------
#
# The service's tick: many tenants' intervals in one launch.  Their host
# windows live on the device in a slab of tenant rings, (slots, T,
# host_dim), one slot per admitted tenant.  Packed staging layout, one
# float32 vector per tick handed to the launch as its own argument:
# [k, beta_scale, present(slots), q(nb), row_slot(nb),
#  new_mh_rows(slots*host_dim), m_t(nb*task_dim)].  ``present`` marks the
# slots whose tenant is in the tick; ``row_slot`` is each job row's slot.


def tenant_layout(n_slots: int, nb: int, host_dim: int,
                  task_dim: int) -> tuple[int, ...]:
    """Offsets of the tenant step's packed vector: where ``present``,
    ``q``, ``row_slot``, the host rows and M_T begin, and its size."""
    present = _N_SCALARS
    q = present + n_slots
    seg = q + nb
    rows = seg + nb
    mt = rows + n_slots * host_dim
    return present, q, seg, rows, mt, mt + nb * task_dim


@functools.partial(jax.jit, donate_argnums=(1,),
                   static_argnames=("nb", "task_dim", "use_pallas",
                                    "per_task", "unroll"))
def _fused_step_tenants(params, slab, packed, *, nb: int, task_dim: int,
                        use_pallas: bool | str = False,
                        per_task: bool = False, unroll: int = 1):
    """:func:`_fused_step` for many tenants at once (Tier-1): rolls each
    present slot of the donated slab by its tenant's staged row (absent
    slots keep their ring), encodes each slot's EMA window once
    (``net.encoder_segmented``), gathers it to the slot's job rows, and
    runs the scan and the tail over all rows.  Returns ``(new_slab,
    out)`` with ``out`` as :func:`_fused_step` returns it."""
    n_slots, _, host_dim = slab.shape
    o_p, o_q, o_seg, o_rows, o_mt, _ = tenant_layout(n_slots, nb, host_dim,
                                                     task_dim)
    k, beta_scale = packed[0], packed[1]
    present = packed[o_p:o_q] > 0.0
    q = packed[o_q:o_seg]
    seg = packed[o_seg:o_rows].astype(jnp.int32)
    rows = packed[o_rows:o_mt].reshape(n_slots, 1, host_dim)
    mt = packed[o_mt:].reshape(nb, task_dim)
    rolled = jnp.concatenate([slab[:, 1:], rows], axis=1)
    slab2 = jnp.where(present[:, None, None], rolled, slab)
    mh_ema = net.ema_smooth(jnp.swapaxes(slab2, 0, 1))   # (T, slots, hd)
    lam = net.encoder_segmented(params, mh_ema, mt, seg)  # (T, nb, E)
    return slab2, _decode_tail(params, lam, k, beta_scale, q, mt, nb=nb,
                               task_dim=task_dim, use_pallas=use_pallas,
                               per_task=per_task, unroll=unroll)


@functools.partial(jax.jit, donate_argnums=(0,))
def _slot_write(slab, packed):
    """Rebuild one slot of the tenant slab: ``packed`` is ``[slot,
    window(T*host_dim)]``, the window its tenant's host history holds."""
    slot = packed[0].astype(jnp.int32)
    return slab.at[slot].set(packed[1:].reshape(slab.shape[1:]))


@functools.partial(jax.jit, donate_argnums=(0,))
def _ring_roll(ring, row):
    """Catch-up roll for intervals that observed hosts but ran no predict
    (idle intervals): absorb one pending M_H row into the device ring."""
    return jnp.concatenate([ring[1:], row[None]], axis=0)


def fused_compile_count() -> int:
    """Cumulative XLA compiles of the fused-step programs (process-wide):
    the fused step itself (Pareto tail and per-task head now live inside
    it), the ring catch-up roll, the service's tenant step and its slot
    rebuild, and the unfused per-task tail — the zero-retrace warm
    accounting covers every Tier-1 entry point."""
    return (_fused_step._cache_size() + _ring_roll._cache_size()
            + _fused_step_tenants._cache_size() + _slot_write._cache_size()
            + _pareto_tail_per_task._cache_size())


@jax.jit
def _pareto_tail(ab: jax.Array, q: jax.Array, k: jax.Array,
                 beta_scale: jax.Array):
    """(alpha, beta) head outputs -> (alpha, beta, K, E_S), fused.

    Kept op-for-op identical to the historical eager chain
    (``straggler_threshold`` + ``expected_stragglers``) so results are
    bitwise-stable; jitting it replaces ~10 per-interval eager dispatches
    (each a compile per batch bucket) with one cached call.
    """
    alpha = ab[..., 0]
    beta = ab[..., 1] * beta_scale
    thr = k * (alpha * beta / (alpha - 1.0))
    kk = thr / beta
    e_s = q * kk ** (-alpha)
    return alpha, beta, thr, e_s


@jax.jit
def _pareto_tail_per_task(ab: jax.Array, q: jax.Array, k: jax.Array,
                          beta_scale: jax.Array, mt: jax.Array):
    """Per-task score tail: (alpha, beta) head + the (nb, task_dim) M_T
    batch -> one packed (nb, 1 + max_tasks) array ``[E_S | scores]``.

    The per-task straggler score decomposes the job-level expected
    straggler count across the job's M_T rows by relative resource
    demand: ``score[j, i] = E_S_j * demand_ji / sum_i demand_ji`` (the
    four requirement columns; the prev-host column is placement, not
    demand).  Scores over a job's real tasks sum exactly to E_S_j —
    with homogeneous demand each task scores the per-task straggler
    probability ``(K/beta)^(-alpha)`` — and zero-padded slots (demand
    0) score 0.  Jobs whose every task reports zero demand fall back to
    a uniform ``E_S / q`` split over their first q slots.

    One jitted program, one packed output: the fused warm path stays a
    single dispatch plus a single readback with the per-task head
    enabled.  Kept separate from ``_pareto_tail`` so the legacy
    E_S-only path keeps its exact cache entry.
    """
    alpha = ab[..., 0]
    beta = ab[..., 1] * beta_scale
    thr = k * (alpha * beta / (alpha - 1.0))
    kk = thr / beta
    e_s = q * kk ** (-alpha)
    nb = mt.shape[0]
    max_tasks = mt.shape[1] // features.TASK_FEATURES
    mt3 = mt.reshape(nb, max_tasks, features.TASK_FEATURES)
    demand = mt3[..., :4].sum(axis=-1)                  # (nb, max_tasks)
    total = demand.sum(axis=-1, keepdims=True)
    real = jnp.arange(max_tasks)[None, :] < q[:, None]  # unpadded slots
    uniform = real / jnp.maximum(q, 1.0)[:, None]
    share = jnp.where(total > 0.0, demand / jnp.where(total > 0.0, total,
                                                      1.0), uniform)
    scores = e_s[:, None] * share
    return jnp.concatenate([e_s[:, None], scores], axis=1)


@dataclasses.dataclass
class StragglerPredictor:
    """Owns Encoder-LSTM params + the (I, T, k) hyper-parameters.

    ``horizon`` is T/I — the number of LSTM iterations per prediction
    (paper: I = 1 s, T = 5 s -> 5 steps).
    """

    n_hosts: int
    max_tasks: int
    k: float = pareto.DEFAULT_K
    horizon: int = 5
    interval: float = 1.0
    seed: int = 0
    # beta (the Pareto scale, in seconds) is regressed in units of
    # beta_scale so the MSE loss is O(1); alpha is O(1) already
    beta_scale: float = 1.0
    # route the LSTM cell through the fused Pallas kernel
    # (repro.kernels.lstm_cell): True runs the compiled Mosaic kernel (a
    # TPU), "interpret" the Pallas interpreter (CPU tests, exact-match
    # tested against the jnp cell).  Applies to inference AND training
    # (fit routes train_step through the same cell).
    use_pallas_cell: bool | str = False

    def __post_init__(self):
        self.input_dim = features.input_dim(self.n_hosts, self.max_tasks)
        self.host_dim = self.n_hosts * features.HOST_FEATURES
        self.task_dim = self.max_tasks * features.TASK_FEATURES
        self._exact_shapes: set[int] = set()
        # params live on device for their whole lifetime — predictions
        # upload only the per-interval feature batch
        self.params = jax.device_put(
            net.init_params(jax.random.PRNGKey(self.seed), self.input_dim))
        self.opt = net.adam_init(self.params)
        self._losses: list[float] = []
        self.buckets_used: set[int] = set()
        self._init_fused_state()

    # ----------------------- fused interval hot path -----------------------

    def _init_fused_state(self) -> None:
        import collections
        self._ring = None          # device-resident (horizon, host_dim) M_H
        self._ring_rows = 0        # host rows the ring has absorbed
        self._host_rows = 0        # host rows observed so far
        #: host-side copy of the last ``horizon`` rows — the source of
        #: truth the device ring is rebuilt from (cold start, unpickling,
        #: error recovery)
        self._row_hist = collections.deque(maxlen=self.horizon)
        self._stage_bufs: dict = {}  # per-bucket staging vectors
        self.h2d_stages = 0        # host->device staging uploads performed
        self.fused_calls = 0       # fused-step dispatches
        self.catchup_rolls = 0     # idle-interval rows rolled in alone
        self.ring_rebuilds = 0     # rings rebuilt from the host history
        self.rows_real = 0         # real job rows over the fused calls
        self.rows_dispatched = 0   # batch rows over the fused calls
        # the service's tenant batch (see open_slab)
        self._slab = None          # device (slots, horizon, host_dim)
        #: host rows each slot has absorbed (None: rebuild on next use)
        self._slot_rows: list = []
        self.tenant_calls = 0            # tenant-step launches
        self.tenant_rows_real = 0        # real job rows over them
        self.tenant_rows_dispatched = 0  # batch rows over them
        self.tenant_slot_rebuilds = 0    # slots rebuilt from a history
        self.tenant_stage_bytes = 0      # bytes of their launch arguments

    def __getstate__(self):
        # the device ring is a pure cache of `_row_hist`; drop it so
        # pickled predictors (the sweep's pretrain broadcast) carry no
        # live device buffers — the clone rebuilds on first predict
        d = dict(self.__dict__)
        d["_ring"] = None
        d["_ring_rows"] = 0
        d["_stage_bufs"] = {}
        d["_slab"] = None
        d["_slot_rows"] = [None] * len(self._slot_rows)
        return d

    def push_host_row(self, m_h: np.ndarray) -> None:
        """Feed one observed host matrix into the fused ring (called every
        interval; the device ring absorbs rows lazily at predict time)."""
        self._row_hist.append(
            np.ascontiguousarray(m_h, np.float32).reshape(-1))
        self._host_rows += 1

    def _stage(self, arr: np.ndarray) -> jax.Array:
        """A host->device upload outside the warm interval: ring rebuilds,
        catch-up rows and tenant slot rebuilds.  Counted in
        ``h2d_stages`` with the warm interval's own upload (see
        :meth:`_launch`), and centralised so the zero-transfer tests can
        wrap it in a scoped ``jax.transfer_guard_host_to_device('allow')``
        while pinning the rest under ``'disallow'`` — the guard context is
        deliberately NOT entered here in production: it costs ~0.2 ms per
        entry, an order of magnitude more than the upload itself.

        The upload goes through a jitted identity rather than
        ``jax.device_put``: the transfer itself is identical, but the pjit
        C++ fast path skips ~0.1 ms of Python ``device_put`` API overhead
        per call — pure dispatch cost, zero numeric difference."""
        self.h2d_stages += 1
        return _stage_put(arr)

    def _launch(self, ring: jax.Array, buf: np.ndarray, nb: int,
                per_task: bool, step=None):
        """Launch the fused step (or ``step``, the tenant step, over the
        tenant slab as ``ring``): the warm interval's single sanctioned
        host->device upload, the packed staging vector ``buf`` handed to
        the launch as its own argument (no separate upload program).
        Counted in ``h2d_stages``; the zero-transfer tests wrap this call
        as they wrap :meth:`_stage`.  Returns ``(new_ring, out)``."""
        self.h2d_stages += 1
        return (step or _fused_step)(
            self.params, ring, buf, nb=nb, task_dim=self.task_dim,
            use_pallas=self.use_pallas_cell, per_task=per_task,
            unroll=self._unroll(nb))

    # ------------------------- Tier-1 batch shaping ------------------------

    def batch_size(self, n: int) -> int:
        """The batch axis the jitted programs see for ``n`` real jobs.

        Power-of-two bucketing keeps the compile count bounded; when the
        bucket would waste more than ``PAD_WASTE_LIMIT`` of its rows the
        exact count is used instead — up to ``EXACT_SHAPES_LIMIT``
        distinct exact shapes, after which new counts pad again (a
        long-lived process must not compile without bound).  Decisions
        are a pure function of the call sequence, so replaying a
        workload replays the shapes — serial == parallel sweeps and
        warm-cell zero-retrace accounting survive."""
        n = int(n)
        nb = bucket_size(n)
        if n and nb > n and (nb - n) / nb > PAD_WASTE_LIMIT:
            if n in self._exact_shapes \
                    or len(self._exact_shapes) < EXACT_SHAPES_LIMIT:
                self._exact_shapes.add(n)
                return n
        return nb

    def _unroll(self, nb: int) -> int:
        """Scan-unroll factor of the fused programs at batch ``nb``: 2,
        or the horizon if shorter — measured fastest across the
        small-batch range (unroll=1 pays scan while-loop machinery per
        step; full unroll at T=5 inflates the program enough that
        dispatch gets slower, not faster).  A constant, never
        timing-derived, so every process runs identical programs."""
        return min(2, self.horizon)

    @property
    def fused_ready(self) -> bool:
        """True when a fresh (unconsumed) host row is staged — the fused
        step rolls exactly one new row per call, so a second predict in
        the same interval must take the unfused path instead."""
        return self._host_rows > self._ring_rows

    def _sync_ring(self) -> np.ndarray:
        """Absorb unconsumed host rows into the device ring, leaving
        exactly one (the newest) for the fused step itself to roll in.
        Returns that last row.  Rebuilds from the host history (one
        upload) when the ring is cold, was dropped by pickling, or fell
        behind by a full horizon."""
        t = self.horizon
        lag = self._host_rows - self._ring_rows
        if lag <= 0 or not self._row_hist:
            raise RuntimeError("no fresh host row to predict from")
        rows = list(self._row_hist)
        if self._ring is None or lag > len(rows):
            # cold start / fell behind: rebuild the ring at "all but the
            # newest row", replaying the host deque's
            # left-pad-with-oldest semantics
            hist = rows[:-1] or rows[:1]
            while len(hist) < t:
                hist.insert(0, hist[0])
            self._ring = self._stage(np.stack(hist[-t:]))
            self.ring_rebuilds += 1
        else:
            # roll in every lagging row but the newest (idle-interval
            # catch-up; the common warm interval has exactly one).  The
            # ring is donated into each roll, so detach it first: if a
            # roll fails mid-way the attribute is None and the next call
            # rebuilds instead of re-using a donated-invalid buffer.
            ring, self._ring = self._ring, None
            for row in rows[-lag:-1]:
                ring = _ring_roll(ring, self._stage(row))
                self.catchup_rolls += 1
            self._ring = ring
        self._ring_rows = self._host_rows - 1
        return rows[-1]

    def predict_interval(self, m_t: np.ndarray, q: np.ndarray,
                         per_task: bool = False):
        """Fused per-interval prediction (Tier-1): ONE jitted device
        program — Pareto tail included — that takes the packed staging
        vector as its own argument (the one upload), then one readback
        of its result (the one download).

        Args:
            m_t: (n, max_tasks, TASK_FEATURES) current task matrices.
            q: (n,) true task counts.
            per_task: also compute the per-task straggler scores.
                Returns ``(e_s, scores)`` with ``scores`` of shape
                ``(n, max_tasks)`` from the fused program's packed
                ``[E_S | scores]`` output; still one launch and one
                readback — the zero-H2D guarantee is unchanged.
        """
        n = m_t.shape[0]
        nb = self.batch_size(n)
        with span("predictor.interval", n=n, nb=nb):
            self.buckets_used.add(nb)
            with span("predictor.sync_ring", n=n, nb=nb):
                row = self._sync_ring()
            with span("predictor.pack", n=n, nb=nb):
                buf = self._pack(row, m_t, q, n, nb)
            with span("predictor.dispatch", n=n, nb=nb):
                # donated: invalid on failure
                ring, self._ring = self._ring, None
                try:
                    ring2, out = self._launch(ring, buf, nb, per_task)
                except Exception:
                    self._ring_rows = 0          # next call rebuilds the ring
                    raise
                self._ring = ring2
                self._ring_rows += 1
                self.fused_calls += 1
                self.rows_real += n
                self.rows_dispatched += nb
            with span("predictor.readback", n=n, nb=nb):
                out = np.asarray(out)
        if per_task:
            # packed [E_S | scores] computed inside the fused program —
            # one readback, no second dispatch
            return out[:n, 0], out[:n, 1:]
        return out[:n]

    def _pack(self, row: np.ndarray, m_t: np.ndarray, q: np.ndarray,
              n: int, nb: int) -> np.ndarray:
        """Fill the bucket's staging buffer (the packed layout of
        ``_fused_step``) with the scalars, the newest host row, q and the
        M_T batch, padded to ``nb`` rows."""
        host_dim = self.host_dim
        task_dim = self.task_dim
        size = _N_SCALARS + host_dim + nb * (1 + task_dim)
        buf = self._stage_bufs.get(nb)
        if buf is None or buf.shape[0] != size:
            buf = self._stage_bufs[nb] = np.zeros(size, np.float32)
        buf[0] = np.float32(self.k)
        buf[1] = np.float32(self.beta_scale)
        buf[_N_SCALARS:_N_SCALARS + host_dim] = row
        qs = buf[_N_SCALARS + host_dim:_N_SCALARS + host_dim + nb]
        qs[:n] = np.asarray(q, np.float32)
        qs[n:] = 1.0
        mt = buf[_N_SCALARS + host_dim + nb:]
        mt[:n * task_dim] = np.asarray(m_t, np.float32).reshape(-1)
        mt[n * task_dim:] = 0.0
        return buf

    # ------------------------ multi-tenant serving -------------------------

    def open_slab(self, n_slots: int) -> None:
        """Serve the tenant batch from ``n_slots`` tenant rings on the
        device, every slot empty (its first use rebuilds it)."""
        self._slab = None
        self._slot_rows = [None] * n_slots

    def forget_slot(self, slot: int) -> None:
        """``slot`` changes hands: its next use rebuilds it."""
        self._slot_rows[slot] = None

    def _sync_slot(self, slot: int, rows_seen: int, hist) -> np.ndarray:
        """Bring ``slot`` to all but the newest of the ``rows_seen`` host
        rows its tenant has observed, and return that newest row for the
        tenant step to roll in.  ``hist`` (the tenant's last ``horizon``
        host matrices, oldest first) is the truth and the slot a cache of
        it: a slot newly taken, lost, or behind by more than one row is
        rebuilt from it (one upload), left-padded with its oldest row as
        the controller pads."""
        rows = list(hist)
        if self._slab is None:
            self._slab = jnp.zeros((len(self._slot_rows), self.horizon,
                                    self.host_dim), jnp.float32)
            self._slot_rows = [None] * len(self._slot_rows)
        if self._slot_rows[slot] != rows_seen - 1:
            win = rows[:-1] or rows[:1]
            win = [win[0]] * (self.horizon - len(win)) + win
            buf = np.empty(1 + self.horizon * self.host_dim, np.float32)
            buf[0] = slot
            buf[1:] = np.reshape(win, -1)
            slab, self._slab = self._slab, None     # donated
            self._slab = _slot_write(slab, self._stage(buf))
            self.tenant_slot_rebuilds += 1
        self._slot_rows[slot] = rows_seen
        return rows[-1]

    def pack_tenants(self, group: list) -> tuple[np.ndarray, int, int]:
        """The tenant step's staging vector for one tick, after bringing
        each tenant's slot up to date (:meth:`_sync_slot`).

        Args:
            group: per tenant of the tick ``(slot, rows_seen, hist, m_t,
                q)``: its slot, the host rows it has observed, its host
                history, its ``(n_i, max_tasks, TASK_FEATURES)`` task
                matrices and ``(n_i,)`` task counts (``n_i`` may be 0).

        Returns ``(buf, nb, n)``: the vector, the dispatched rows (the
        power-of-two bucket of the ``n`` real ones; padding rows take the
        last real row's slot, q 1 and a zero M_T).  Only new rows cross:
        one host row per slot, and M_T, q and a slot index per row."""
        n_slots = len(self._slot_rows)
        n = sum(len(g[4]) for g in group)
        nb = bucket_size(n)
        o_p, o_q, o_seg, o_rows, o_mt, size = tenant_layout(
            n_slots, nb, self.host_dim, self.task_dim)
        buf = self._stage_bufs.get(("tenants", nb))
        if buf is None or buf.shape[0] != size:
            buf = self._stage_bufs[("tenants", nb)] = np.zeros(size,
                                                               np.float32)
        buf[0] = np.float32(self.k)
        buf[1] = np.float32(self.beta_scale)
        buf[o_p:o_q] = 0.0
        rows = buf[o_rows:o_mt].reshape(n_slots, self.host_dim)
        mt = buf[o_mt:].reshape(nb, self.task_dim)
        lo, last = 0, group[-1][0]
        try:
            for slot, rows_seen, hist, m_t, q in group:
                rows[slot] = self._sync_slot(slot, rows_seen,
                                             hist).reshape(-1)
                buf[o_p + slot] = 1.0
                hi = lo + len(q)
                if hi > lo:
                    buf[o_q + lo:o_q + hi] = q
                    buf[o_seg + lo:o_seg + hi] = last = slot
                    mt[lo:hi] = np.reshape(m_t, (hi - lo, self.task_dim))
                lo = hi
        except BaseException:
            self._slab = None       # a half-synced slab: rebuild them all
            raise
        buf[o_q + n:o_seg] = 1.0
        buf[o_seg + n:o_rows] = last
        mt[n:] = 0.0
        return buf, nb, n

    def predict_tenants(self, buf: np.ndarray, nb: int, n: int, g: int,
                        per_task: bool = False) -> np.ndarray:
        """The service's tick (Tier-1): one launch of the tenant step on
        the staging vector of :meth:`pack_tenants` (``n`` real rows of
        ``g`` tenants), then one readback.  Returns E_S of the ``n``
        rows in the packing's order, or ``(n, 1 + max_tasks)`` ``[E_S |
        scores]`` when ``per_task``."""
        with span("predictor.tenants", g=g, n=n, nb=nb):
            slab, self._slab = self._slab, None     # donated
            self.buckets_used.add(nb)
            self._slab, out = self._launch(slab, buf, nb, per_task,
                                           step=_fused_step_tenants)
            self.tenant_calls += 1
            self.tenant_rows_real += n
            self.tenant_rows_dispatched += nb
            self.tenant_stage_bytes += buf.nbytes
            return np.asarray(out)[:n]

    # ---------------------------- inference -------------------------------

    def predict_features(self, m_h_seq: np.ndarray, m_t: np.ndarray,
                         q: np.ndarray, per_task: bool = False):
        """Predict (alpha, beta, K, E_S) for a batch of jobs from numpy
        feature matrices (the simulator hot path).

        Args:
            m_h_seq: (T, n_hosts, HOST_FEATURES) shared host history.
            m_t: (jobs, max_tasks, TASK_FEATURES) current task matrices
                (broadcast across T — the engine publishes one M_T per
                decision point).
            q: (jobs,) true task counts.
            per_task: return ``(e_s, scores)`` from the per-task score
                tail instead of a :class:`Prediction` — the unfused
                mirror of ``predict_interval(..., per_task=True)``.  Both
                paths feed bitwise-identical (ab, q, k, beta_scale, M_T)
                into the same ``_pareto_tail_per_task`` cache entry, so
                their outputs are bitwise-equal (tested per shape).

        The job axis is zero-padded to a power-of-two bucket before the
        jitted network; padded rows are masked off the returned arrays.
        """
        n = m_t.shape[0]
        return self._predict_bucketed(
            m_h_seq, np.asarray(m_t, np.float32).reshape(1, n, -1), n, q,
            per_task=per_task)

    def predict(self, m_h_seq: jax.Array, m_t_seq: jax.Array,
                q: jax.Array) -> Prediction:
        """Predict from full (T, jobs, ...) matrix sequences (general API;
        tolerates time-varying task matrices).

        Args:
            m_h_seq: (T, n_hosts, HOST_FEATURES) shared host history.
            m_t_seq: (T, jobs, max_tasks, TASK_FEATURES) per-job history.
            q: (jobs,) true task counts.
        """
        t, jobs = m_t_seq.shape[0], m_t_seq.shape[1]
        return self._predict_bucketed(
            m_h_seq, np.asarray(m_t_seq, np.float32).reshape(t, jobs, -1),
            jobs, q)

    def _predict_bucketed(self, m_h_seq: np.ndarray, mt_flat: np.ndarray,
                          n: int, q: np.ndarray, per_task: bool = False):
        """Shared bucketing contract: assemble the (T, bucket, input_dim)
        batch — host features on every row, task features zero-padded
        past ``n``, q padded with 1.0 — run the jitted network, and mask
        the padded rows off the outputs.  ``mt_flat`` is (1|T, n, -1)
        flattened task features (broadcast across T when 1)."""
        t = m_h_seq.shape[0]
        nb = bucket_size(n)
        self.buckets_used.add(nb)
        mh_flat = np.asarray(m_h_seq, np.float32).reshape(t, 1, -1)
        host_dim = mh_flat.shape[-1]
        xs = np.zeros((t, nb, self.input_dim), np.float32)
        xs[:, :, :host_dim] = mh_flat
        xs[:, :n, host_dim:] = mt_flat
        qp = np.ones(nb, np.float32)
        qp[:n] = np.asarray(q, np.float32)
        if per_task:
            # the padded task block of the last step IS the fused path's
            # staged M_T batch (raw features, zero past n), so the shared
            # tail sees bitwise-identical inputs on both paths
            ab = net.predict_sequence(self.params, jnp.asarray(xs),
                                      use_pallas=self.use_pallas_cell)
            out = np.asarray(_pareto_tail_per_task(
                ab, jnp.asarray(qp), jnp.float32(self.k),
                jnp.float32(self.beta_scale),
                jnp.asarray(xs[-1, :, host_dim:])))
            return out[:n, 0], out[:n, 1:]
        pred = self._predict_xs(xs, qp)
        return Prediction(*(np.asarray(f)[:n] for f in pred))

    def _predict_xs(self, xs: np.ndarray, q: np.ndarray) -> Prediction:
        ab = net.predict_sequence(self.params, jnp.asarray(xs),
                                  use_pallas=self.use_pallas_cell)
        alpha, beta, thr, e_s = _pareto_tail(
            ab, jnp.asarray(q), jnp.float32(self.k),
            jnp.float32(self.beta_scale))
        return Prediction(alpha=alpha, beta=beta, threshold=thr, e_s=e_s)

    @property
    def compile_count(self) -> int:
        """Cumulative XLA compiles of the jitted prediction programs in
        this process — the unfused network plus the fused interval step
        (spanning every predictor instance — jit caches are global)."""
        return net.predict_sequence._cache_size() + fused_compile_count()

    # ---------------------------- training --------------------------------

    def make_targets(self, times: jax.Array, mask: jax.Array | None = None
                     ) -> jax.Array:
        """MLE-fit (alpha, beta/beta_scale) targets from response times."""
        a, b = pareto.fit_pareto(times, mask)
        return jnp.stack([a, b / self.beta_scale], axis=-1)

    def fit(self, xs: jax.Array, targets: jax.Array, epochs: int = 50,
            lr: float = 1e-5, batch: int = 64,
            use_pallas_cell: bool | str | None = None) -> list[float]:
        """Train on (T, N, input_dim) sequences vs (N, 2) targets.

        Minibatches keep one shape: when N > batch the trailing partial
        batch is dropped (each epoch re-permutes, so all data is seen
        across epochs) instead of retracing ``train_step`` on a second
        shape; when N <= batch the single batch is the whole set.
        Records the epoch-mean loss, not the last batch's.

        ``use_pallas_cell`` routes the forward (and, through autodiff,
        the backward) pass of every ``train_step`` through the fused
        Pallas LSTM cell; ``None`` follows the predictor's flag.
        """
        n = xs.shape[1]
        use_pallas = (self.use_pallas_cell if use_pallas_cell is None
                      else use_pallas_cell)
        rng = np.random.default_rng(self.seed)
        xs = jnp.asarray(xs)           # resident on device across epochs
        targets = jnp.asarray(targets)
        for _ in range(epochs):
            order = rng.permutation(n)
            if n > batch:
                order = order[:n - (n % batch)]
            losses = []
            for s in range(0, len(order), batch):
                idx = order[s:s + batch]
                self.params, self.opt, loss = net.train_step(
                    self.params, self.opt, xs[:, idx], targets[idx], lr=lr,
                    use_pallas=use_pallas)
                losses.append(float(loss))
            self._losses.append(float(np.mean(losses)))
        return self._losses

    @property
    def losses(self) -> list[float]:
        return self._losses
