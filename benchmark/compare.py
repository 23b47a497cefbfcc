"""How ``correct`` is decided: every answer the timed path produced in the
window, against the plain reference (``benchmark.reference``) solving the
same problem.

The numbers compared, each against the limit its configuration file
states under ``limits``:

- ``w_rel_err``: the largest ‖w − w_ref‖₂ / ‖w_ref‖₂ over the answers,
  on the whole node grid, in float64;
- ``unanswered``: answers that were due and never came, came unconverged
  or were refused (limit 0).

``iters_gap``, the largest |iterations − the reference's|, is printed
beside them as a reading.
"""

from __future__ import annotations

import sys

import numpy as np

from benchmark import reference

# what an answer of the wrong shape, or with a NaN or inf in it, reads
UNUSABLE = 1e9


def readings(config: dict, answers: list, device,
             unanswered: int = 0) -> dict:
    """The compared numbers for ``answers`` (dicts with ``eps``, ``w``,
    ``iters``, ``converged``). Each distinct ε is solved once by the
    reference."""
    refs = {}
    err, gap = 0.0, 0
    for ans in answers:
        key = ans["eps"]
        if key not in refs:
            spec = reference.problem_spec(config, key)
            w_ref, k_ref, conv = reference.solve(spec, device=device)
            if not conv:
                raise RuntimeError(f"the reference did not converge at "
                                   f"eps={key}")
            refs[key] = (w_ref, np.linalg.norm(w_ref), k_ref)
        w_ref, norm, k_ref = refs[key]
        w = np.asarray(ans["w"], np.float64)
        if w.shape != w_ref.shape or not np.all(np.isfinite(w)):
            err = UNUSABLE
        else:
            err = max(err, float(np.linalg.norm(w - w_ref) / norm))
        gap = max(gap, abs(int(ans["iters"]) - k_ref))
        if not ans["converged"]:
            unanswered += 1
    return {"w_rel_err": err, "unanswered": unanswered, "iters_gap": gap}


def check(config: dict, answers: list, device, unanswered: int = 0,
          log=sys.stderr) -> dict:
    """{name: (reading, limit)} for every number the configuration
    limits; the other readings go to ``log``."""
    got = readings(config, answers, device, unanswered)
    limits = dict(config["limits"], unanswered=0)
    for name, value in got.items():
        if name not in limits:
            print(f"reading {name}: {value!r}", file=log)
    if not answers:
        got["unanswered"] = max(got["unanswered"], 1)
    return {name: (got[name], limits[name]) for name in limits}


def passed(checks: dict) -> bool:
    return all(value <= limit for value, limit in checks.values())
