"""The comparison that decides ``correct``.

The program's answer to a request is compared with the plain reference
(:mod:`refmap`) on the same request:

- ``placement_mismatch``: ranks placed on another core than the
  reference places them (covers the partition engine, the matching,
  the rotation search's winner and, in the ``node`` hierarchy, the
  swap refinement and the core expansion);
- ``winner_mismatch``: 1 where the winning rotation is another than
  the reference's (covers the scorer's every objective column, since
  the winner is the lexicographically smallest row of them);
- ``objective_gap``: the reported objective's distance from the
  reference winner's, as a share of the latter (covers the scorer; the
  service reports the winner's first column only, and in the ``node``
  hierarchy every column after every refinement round);
- ``refine_history_gap`` and ``refine_steps_mismatch`` (``node``
  only): the objective after each refinement round, as a share of the
  reference's, and the difference in rounds run plus swaps accepted.

Each is an exact comparison, so each limit is 0.  A request that
raised counts in ``failed_requests``, one that the service answered
from a lower rung of its degradation ladder (an operation failed under
it) in ``degraded_requests``, a program compiled inside the window in
``window_compiles``, and a request answered from the result cache (not
cold) in ``repeat_requests``: all held to 0 too.  The
readings these limits were set from are in ``PERF.md``.
"""

from __future__ import annotations

import numpy as np

LIMITS = {
    "placement_mismatch": 0,
    "winner_mismatch": 0,
    "objective_gap": 0.0,
    "refine_history_gap": 0.0,
    "refine_steps_mismatch": 0,
    "failed_requests": 0,
    "degraded_requests": 0,
    "window_compiles": 0,
    "repeat_requests": 0,
}


def _gap(a: float, b: float) -> float:
    return abs(float(a) - float(b)) / max(abs(float(b)), 1e-300)


def _rotation(rot, dims: tuple) -> tuple:
    """A (task_perm, proc_perm) pair, the identity written out."""
    return tuple(tuple(p) if len(p) else tuple(range(d))
                 for p, d in zip(rot, dims))


def compare(answer: dict, ref: dict, hierarchy: str) -> dict:
    """The numbers of one request."""
    prog = np.asarray(answer["task_to_core"])
    dims = tuple(len(p) for p in ref["rotation"])
    out = {
        "placement_mismatch": int(np.sum(prog != ref["task_to_core"]))
        if prog.shape == ref["task_to_core"].shape else int(prog.size),
        "winner_mismatch": int(_rotation(answer["rotation"], dims)
                               != _rotation(ref["rotation"], dims)),
        "objective_gap": _gap(answer["objective"], ref["objective"][0]),
    }
    if hierarchy == "node":
        hp = [list(h) for h in (answer["history"] or [])]
        hr = ref["history"]
        out["refine_history_gap"] = max(
            [_gap(a, b) for x, y in zip(hp, hr) for a, b in zip(x, y)],
            default=0.0)
        out["refine_steps_mismatch"] = (
            abs(len(hp) - len(hr))
            + abs(int(answer["accepted"] or 0) - int(ref["accepted"])))
    return out


def worst(rows: list) -> dict:
    """The largest reading of each number over the compared requests."""
    out: dict = {}
    for row in rows:
        for k, v in row.items():
            out[k] = max(out.get(k, v), v)
    return out


def verdict(numbers: dict) -> tuple:
    """``(correct, checks)``: each number beside its limit, in order."""
    checks = {k: {"value": numbers[k], "limit": LIMITS[k]}
              for k in LIMITS if k in numbers}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
