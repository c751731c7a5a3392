"""Plain reference of the two-tier paged KV store holding MLA latent pages.

The replay is `store_ref.run`'s, unchanged: the store's decisions do not
depend on what a page holds, only on what it costs on the link. What this
layout changes is that cost. A latent page holds, for each token, one
vector of `kv_lora_rank + qk_rope_head_dim` bfloat16 values (the
compressed c_KV and the shared RoPE key that multi-head latent attention
caches; DeepSeek-V2, arXiv:2405.04434, section 2.1), and no separate K
and V: (512 + 64) x 2 = 1152 bytes a token for DeepSeek-V3. It imports
nothing of the program under test.
"""
from __future__ import annotations

from reference import store_ref

LEDGER_KEYS = store_ref.LEDGER_KEYS
run = store_ref.run


class StoreSpec(store_ref.StoreSpec):
    """The store's parameters, as the latent configuration states them
    (its model numbers at the top level, as the published config has
    them)."""

    def __init__(self, cfg: dict, batch: int):
        st = cfg["store"]
        self.B = batch
        self.N = st["local_pages_per_tenant"]
        self.W = st["pool_ways"]
        self.S = self.N // self.W
        dp = cfg["daemon"]
        self.P = dp["inflight_page_buf"]
        self.SB = dp["inflight_sb_buf"]
        self.M = st["memory_modules"]
        self.page_tokens = st["page_tokens"]
        self.budget = st["page_budget_per_step"]
        self.ratio = dp["bw_ratio"]
        self.lpp = dp["page_bytes"] // dp["line_bytes"]
        self.dirty_threshold = dp["dirty_flush_threshold"]
        token_bytes = float((cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
                            * 2)
        raw_page = self.page_tokens * token_bytes
        self.line_wire = token_bytes                  # one token, raw
        self.page_raw = raw_page
        # int8 link compression: int8 payload + one f32 scale per 256
        self.page_wire = (raw_page / 2 + raw_page / 2 / 256 * 4
                          if st["compress_pages"] else raw_page)
        self.bw = self.budget * token_bytes / (1.0 - self.ratio)
        self.nominal = float(max(1, round(self.page_tokens / self.budget)))
