"""The chunked-scan kernel's share of its roofline: the least time the chip
could take to read a prefill's ``q``, ``k``, ``v``, ``g``, ``beta`` and
write its output once at float32, the states read and written once, and to
do the WY form's operations once (chipbench.delta_scan_need; memory bound by
that count) over the time the traced ``delta_scan`` calls took.  Shapes from
``linear_num_key_heads``, ``linear_num_value_heads``, ``linear_key_head_dim``
and ``linear_value_head_dim`` (Gated DeltaNet: a decay a head).  A program
without the kernel, as the parent of PR 42 is, a prefill on the
``jax.numpy`` form (a decay a channel), a traced slice without a prefill and
a configuration without those keys report nothing."""

from chipbench import delta_scan_need


def read(run):
    cfg = run.ctx.config
    if (not run.trace or run.peak is None
            or "linear_num_value_heads" not in cfg):
        return None
    return delta_scan_need.roofline_share(
        run.trace, cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
        cfg["linear_key_head_dim"], cfg["linear_value_head_dim"], run.peak)
