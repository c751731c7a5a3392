"""Bytes the store's residency transaction needs, from shapes and counts.

Per decode step the transaction reads and writes every store's (sets x
ways) page table: five 4-byte fields a slot (page, age, ready, dirty,
re-reference value). Each page that lands reads its K and V rows from the
remote tier and writes them into the pool; each hit reads its K and V
rows from the pool and writes them to the served output. Landings are
counted by the evictions they caused, so a landing into an empty slot is
left out: the count is a lower bound, and so is the share of the
roofline it gives.
"""
from __future__ import annotations

META_FIELD_BYTES = 4 * 5


def residency_bytes(ctx: dict) -> float:
    d = ctx["ledger_delta"]
    meta = 2.0 * ctx["units"] * ctx["stores"] * ctx["slots"] * \
        META_FIELD_BYTES
    row_moves = 2.0 * 2.0 * ctx["row_bytes"]         # K and V, read + write
    return meta + row_moves * (d["evictions"] + d["local_hits"])


def roofline_pct(nbytes: float, seconds: float, device_kind: str) -> float:
    from peaks import peaks
    return 100.0 * nbytes / peaks(device_kind)["hbm_bw"] / seconds
