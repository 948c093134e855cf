"""Shared benchmark utilities: CSV artifact emission."""
from __future__ import annotations

import csv
import os

ARTIFACTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "artifacts")


def write_csv(name: str, header: list[str], rows: list[list]) -> str:
    os.makedirs(ARTIFACTS, exist_ok=True)
    path = os.path.join(ARTIFACTS, name)
    with open(path, "w", newline="") as f:
        w = csv.writer(f)
        w.writerow(header)
        w.writerows(rows)
    return path

