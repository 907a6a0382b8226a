"""The latent decode-attention kernel's share of its roofline: the least time
the chip could take for the busy slots' resident latent columns and query
rows (chipbench.mla_need, from ``SlotEngine.stats()["decode_need"]``'s window
means) over the time the traced ``latent_decode_attention`` calls took.
Widths from ``num_attention_heads``, ``kv_lora_rank`` and
``qk_rope_head_dim``.  A program without the kernel or the counter, as the
parent of PR 32 is, and a decode step on the dense branch report nothing."""

from chipbench import mla_need


def read(run):
    need = run.counters.get("engine", {}).get("decode_need")
    cfg = run.ctx.config
    if (not run.trace or run.peak is None or not need
            or "kv_lora_rank" not in cfg):
        return None
    return mla_need.roofline_share(
        run.trace, need, cfg["num_attention_heads"],
        cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"], cfg["kv_lora_rank"],
        run.peak)
