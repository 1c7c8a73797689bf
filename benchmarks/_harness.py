"""Shared machinery for the measured benchmark gates in ``micro.py``.

Every gate is the same shape: a ``<name>_bench()`` that returns a JSON
dict, a ``check_<name>_regression(baseline_path)`` that recomputes the
machine-independent surfaces and exits nonzero on regression, and a CLI
pair ``--<name>-json PATH`` (refresh the committed baseline, wall time
included) / ``--<name>-check`` (the CI gate). This module holds what the
gates used to repeat verbatim:

* the placeholder-mesh subprocess runner (the bench process itself must
  keep the single real CPU device, so anything needing
  ``xla_force_host_platform_device_count`` runs in a child),
* the drift-vs-baseline comparison for pure-python sections (schedule
  shapes, cost-model floats, byte counts — machine-independent, so any
  mismatch means the code changed and the baseline must be refreshed
  alongside),
* the failure report / exit-code convention, and
* the argparse + dispatch plumbing that maps gate registrations onto
  the CLI.

Must import clean with runtime deps only (the CI bench jobs run
``pip install -e .`` without ``[dev]`` and assert exactly that).
"""
from __future__ import annotations

import dataclasses
import json
import os
import subprocess
import sys
from typing import Callable, Dict, List, Optional, Sequence


def run_py_subprocess(script: str, *, label: str, timeout: int = 900) -> Dict:
    """Run ``python -c script`` and parse the JSON object it prints on
    its last stdout line. The child typically sets
    ``--xla_force_host_platform_device_count`` before importing jax to
    get a placeholder multi-device mesh. It runs pinned to the CPU
    (``JAX_PLATFORMS=cpu``), so on a host with a chip it never competes
    with its parent for the device."""
    proc = subprocess.run([sys.executable, "-c", script],
                          capture_output=True, text=True, timeout=timeout,
                          env={**os.environ, "JAX_PLATFORMS": "cpu"})
    if proc.returncode != 0:
        raise RuntimeError(
            f"{label} subprocess failed:\n{proc.stdout}\n{proc.stderr}")
    return json.loads(proc.stdout.strip().splitlines()[-1])


def drift_check(failures: List[str], cur: Dict, base: Dict,
                keys: Sequence[str], *, baseline: str,
                section: str = "") -> None:
    """Compare the listed keys of ``cur`` against the committed baseline
    and append one failure per mismatch. Only use for machine-independent
    values: the message tells the author to refresh ``baseline`` if the
    change was intentional."""
    prefix = f"{section}." if section else ""
    for k in keys:
        if cur.get(k) != base.get(k):
            failures.append(
                f"{prefix}{k} drifted: {cur.get(k)} != baseline "
                f"{base.get(k)} (refresh {baseline} if intentional)")


def report(name: str, failures: List[str], ok_msg: str) -> int:
    """Print the gate verdict in the house style and return the exit
    code (1 on any failure)."""
    for msg in failures:
        print(f"{name.upper()} BENCH REGRESSION: {msg}")
    if not failures:
        print(f"{name} bench OK: {ok_msg}")
    return 1 if failures else 0


@dataclasses.dataclass(frozen=True)
class Gate:
    """One registered benchmark gate: ``bench`` produces the baseline
    JSON (wall time included), ``check`` takes the committed baseline
    path and returns an exit code. ``print_key`` optionally restricts
    the refresh-mode stdout echo to one section of the result (traces
    can be large)."""
    name: str
    baseline: str
    bench: Callable[[], Dict]
    check: Callable[[str], int]
    json_help: str
    check_help: str
    print_key: Optional[str] = None


def add_cli(ap, gates: Sequence[Gate]) -> None:
    for g in gates:
        ap.add_argument(f"--{g.name}-json", metavar="PATH",
                        help=g.json_help)
        ap.add_argument(f"--{g.name}-check", action="store_true",
                        help=g.check_help)


def dispatch(args, gates: Sequence[Gate], root: str) -> Optional[int]:
    """Run the gate the CLI selected — check mode wins over a refresh —
    or return None when no gate flag was passed (the caller's default
    path runs)."""
    for g in gates:
        if getattr(args, f"{g.name}_check"):
            return g.check(os.path.join(root, g.baseline))
    for g in gates:
        path = getattr(args, f"{g.name}_json")
        if path:
            res = g.bench()
            with open(path, "w") as f:
                json.dump(res, f, indent=2)
                f.write("\n")
            print(json.dumps(res[g.print_key] if g.print_key else res,
                             indent=2))
            return 0
    return None
