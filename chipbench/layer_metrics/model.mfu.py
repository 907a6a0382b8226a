"""Model FLOP/s utilization: tokens a step holds x the operations a token
needs (the configuration's ``flops_per_token``; causal attention at half of
T^2, recomputation not counted) / (median step time x chips x peak).  The
same fact as tokens/s on another scale; reported, never claimed."""

from chipbench import spec
from chipbench.readers import span_median_ms


def read(run):
    step_ms = span_median_ms(run, "train_step")
    if step_ms is None or run.peak is None:
        return None
    per_token = spec.resolve(run.ctx.config["flops_per_token"])(
        run.model_kwargs, run.counters["seq_len"])
    need = run.counters["tokens_per_step"] * per_token
    return 100.0 * need / (step_ms * 1e-3 * run.ctx.chips
                           * run.peak["bf16_flops_per_s"])
