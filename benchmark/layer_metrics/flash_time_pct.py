"""ops: share of the device's busy time in EVERY flash-attention kernel of the
cell, forward and backward — the Mosaic calls lib/hlo.flash_calls lists by the
names the program gives them (``flash_fwd``, ``flash_bwd``, ``flash_bwd_dq``,
``flash_bwd_dkv``; ``swa_*`` on the band path; ``mla_*``), the recomputed
forward's second run included — so ``attn_time_pct`` less this is attention
outside its kernels (in a cell with two attention kinds: their two shares'
sum less this)."""


def read(artifacts):
    summary = artifacts.get("trace_summary")
    calls = artifacts.get("flash_calls")
    if not summary or not calls:
        return None
    seconds = sum(summary["ops"].get(c["name"], {}).get("seconds", 0.0)
                  for c in calls)
    return 100.0 * seconds / summary["busy_s"] if seconds else None
