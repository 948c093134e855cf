"""Quickstart: the START pipeline end-to-end in ~60 lines.

1. Fit a Pareto tail to task times (Eq. 3) and get E_S (Eq. 4).
2. Train the Encoder-LSTM to predict (alpha, beta) from cluster state.
3. Run the cloud simulator with START mitigating stragglers and compare
   against no mitigation.

    PYTHONPATH=src python examples/quickstart.py
"""
import jax

from repro.core import pareto
from repro.sim import Simulation, small
from repro.sim.techniques import START, make
from repro.sim.techniques.start_tech import pretrain

# --- 1. the Pareto straggler model -----------------------------------------
key = jax.random.PRNGKey(0)
times = pareto.sample_pareto(key, alpha=2.0, beta=60.0, shape=(500,))
a, b = pareto.fit_pareto(times)
es = pareto.expected_stragglers(500.0, a, b, k=1.5)
print(f"fitted alpha={float(a):.2f} beta={float(b):.1f}s "
      f"-> E_S={float(es):.1f} expected stragglers / 500 tasks")

# --- 2. train the Encoder-LSTM predictor (paper §4.4) ----------------------
cfg = small(n_hosts=16, n_intervals=60, seed=7)
controller = pretrain(cfg, epochs=10, lr=1e-3)
print(f"predictor trained; final MSE loss "
      f"{controller.predictor.losses[-1]:.4f}")

# --- 3. mitigate stragglers in the simulator -------------------------------
results = {}
for name, tech in (("none", make("none")),
                   ("START", START(controller=controller))):
    sim = Simulation(small(n_hosts=16, n_intervals=80, seed=21),
                     technique=tech)
    results[name] = sim.run()

for name, s in results.items():
    print(f"{name:>6}: exec={s['avg_execution_time_s']:7.1f}s "
          f"sla_viol={s['sla_violation_rate']:.3f} "
          f"energy={s['energy_kwh']:.2f}kWh")
gain = 100 * (1 - results["START"]["avg_execution_time_s"]
              / results["none"]["avg_execution_time_s"])
print(f"START reduces mean execution time by {gain:.1f}%")
