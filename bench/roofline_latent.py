"""Bytes the latent store's two kernels need, from shapes and counts.

The residency transaction reads and writes every store's (sets x ways)
page table, as in `roofline.py`; each page that lands reads its one
latent row from the remote tier and writes it into the pool, and each hit
reads its row from the pool and writes it to the served output. Landings
are the program's own count (`landings`: every page written into the
pool, into an empty slot or not). The remote fetch (`paged_gather`)
reads each gathered row from the tier and writes it out: the program
counts the rows (`remote_rows`). A count the program does not keep gives
None.
"""
from __future__ import annotations

from roofline import META_FIELD_BYTES


def residency_bytes(ctx: dict) -> float | None:
    d = ctx["ledger_delta"]
    if "landings" not in d:
        return None
    meta = 2.0 * ctx["units"] * ctx["stores"] * ctx["slots"] * \
        META_FIELD_BYTES
    return meta + 2.0 * ctx["row_bytes"] * (d["landings"] + d["local_hits"])


def gather_bytes(ctx: dict) -> float | None:
    rows = ctx["ledger_delta"].get("remote_rows")
    return None if rows is None else 2.0 * ctx["row_bytes"] * rows
