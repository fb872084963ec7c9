"""The ``mapscore`` kernel's work, counted from the algorithm.

One call scores ``ncand`` candidate mappings of one message list.  What
the algorithm must do, whatever implements it, and never the padding of
an implementation:

- read each distinct input once: the message weights once per call,
  the two endpoint coordinates of every message once per candidate
  (the router columns for hop counts, every machine column when it
  routes, since a link is indexed by the full coordinate), the inverse
  link bandwidths once per call;
- per message, candidate and router dimension: the hop distance (a
  difference, its absolute value, on a torus the way round and the
  minimum, the running sum), then the weighted sum;
- when it routes (the latency objective): per message, candidate and
  router dimension the direction, the length and the two ends of the
  range added to the link loads; per link of the machine-shaped load
  arrays (both directions) the prefix sum, the load maximum, the
  scaling by the inverse bandwidth and the latency maximum;
- write back four numbers per candidate.

Peaks come from ``peaks.json``, keyed by the device's ``device_kind``;
a device that is not in the table is an error.
"""

from __future__ import annotations

import dataclasses
import json
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclasses.dataclass(frozen=True)
class Work:
    flops: float
    bytes: float


def peaks(device_kind: str) -> dict:
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def mapscore_work(*, ncand: int, messages: int, router_dims, wrap,
                  cores_per_node: int, traffic: bool) -> Work:
    nd = len(router_dims)
    ncols = nd + 1 if traffic else nd
    nbytes = 4.0 * messages                        # weights
    nbytes += ncand * 2.0 * messages * ncols * 4   # endpoints
    nbytes += ncand * 4 * 4.0                      # results
    hop_ops = sum(5 if w else 3 for w in wrap[:nd])
    flops = ncand * messages * (hop_ops + 2)
    if traffic:
        nbytes += 4.0 * sum(router_dims)          # inverse bandwidths
        links = 2 * nd * int(np.prod(router_dims)) * cores_per_node
        flops += ncand * (messages * nd * 8 + links * 4)
    return Work(float(flops), float(nbytes))


def least_seconds(work: Work, peak: dict) -> tuple:
    """``(seconds, bound)``: the least time at the chip's peaks, and
    whether operations or bytes bound it."""
    t_ops = work.flops / float(peak["flops_per_s"])
    t_mem = work.bytes / float(peak["hbm_bytes_per_s"])
    return (t_ops, "operations") if t_ops >= t_mem else (t_mem, "bytes")
