"""Bytes and operations of the row feasibility work, from its shapes.

The work is the line-up power admission test of paper Eq. 1/2/26/27 for
every candidate row of every configuration in a batch: per row, the
HA load, total load, rating and validity of each of its `feeds`
line-ups, the row's balanced share, failover share, power load and
power rating in; one 0/1 flag out; all float32.  Per feed: the
balanced-share test (2 adds, compare), the HA headroom test (2 adds,
product, compare, and), the block test (add, compare), the tier and
topology selects as 0/1 arithmetic (9), the validity select (2) and the
min over feeds (1): 22.  Per row: the row power test (2 adds, compare),
its conversion and the product with the power result: 5.

`least_seconds` is the roofline bound of one pass on a device of
`bench/peaks.json`: the larger of bytes over memory bandwidth and
operations over the published peak rate.
"""
from __future__ import annotations

import json
import os
import re

F32 = 4
ROW_INPUTS = 4            # share, failover share, row load, row rating
FEED_INPUTS = 4           # HA load, total load, rating, validity
OPS_PER_FEED = 22
OPS_PER_ROW = 5

_PEAKS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                      "peaks.json")


def peaks(device_kind: str) -> dict:
    with open(_PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"bench/peaks.json; have {sorted(table)}")
    return table[device_kind]


def feasibility_work(batch: int, feeds: int, rows: int):
    """(bytes, operations) of one pass over `batch` × `rows` rows."""
    n = batch * rows
    return (n * (feeds * FEED_INPUTS + ROW_INPUTS + 1) * F32,
            n * (feeds * OPS_PER_FEED + OPS_PER_ROW))


def least_seconds(batch: int, feeds: int, rows: int, device_kind: str):
    b, ops = feasibility_work(batch, feeds, rows)
    p = peaks(device_kind)
    return max(b / p["hbm_bytes_per_s"], ops / p["bf16_flops_per_s"])


_SHAPE = re.compile(r"f32\[([0-9,]+)\]")


def launch_shape(event_name: str):
    """(batch, feeds, rows) of one kernel launch, read from its HLO text
    in the trace, ``%name = f32[B…, 1, R] custom-call(f32[B…, F, R] …``;
    None where the text has another form."""
    _, _, rest = event_name.partition(" = ")
    dims = [[int(x) for x in m.split(",")] for m in _SHAPE.findall(rest)]
    if len(dims) < 2 or dims[0][-2] != 1 or dims[1][-1] != dims[0][-1]:
        return None
    batch = 1
    for x in dims[0][:-2]:
        batch *= x
    return batch, dims[1][-2], dims[0][-1]
