"""The one-token state-update kernel's share of its roofline: the least time
the chip could take to read and write the busy slots' float32 state once and
their small operands (chipbench.delta_step_need, rows a step from
``SlotEngine.stats()["decode_need"]``'s window means) over the time the
traced ``delta_step`` calls took.  Shapes from ``linear_num_heads`` and
``linear_head_dim`` (Kimi Delta Attention: keys and values alike, a decay a
channel).  A program without the kernel, as the parent of PR 41 is,
a decode step on the ``jax.numpy`` form, a traced slice without a decode step
and a configuration without those keys report nothing."""

from chipbench import delta_step_need


def read(run):
    need = run.counters.get("engine", {}).get("decode_need")
    cfg = run.ctx.config
    if (not run.trace or run.peak is None or not need
            or "linear_head_dim" not in cfg):
        return None
    return delta_step_need.roofline_share(
        run.trace, need, cfg["linear_num_heads"], cfg["linear_head_dim"],
        cfg["linear_head_dim"], run.peak)
