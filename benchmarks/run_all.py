"""Run the full extended benchmark ladder; write BENCH_EXTENDED.json.

Covers BASELINE.md ladder rows measurable in this sandbox:
  #1/#2 headline  — bench.py (MNIST ConvNet, printed by the driver)
  #4              — resnet_cifar (ResNet-18 CIFAR-10 bf16, real chip)
  #2/#3 stand-in  — scaling (virtual-mesh weak-scaling overhead)

Usage:  python -m benchmarks.run_all
"""

from __future__ import annotations

import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

# v5e bf16 peak is ~197 TFLOPs/chip; any row whose model-FLOPs accounting
# implies more than this cap is a timing artifact (the scan-differenced
# minima can cross under heavy drift), not a measurement — the ratchet
# must never lock one in as a best.
_TFLOPS_CAP = 185.0


_HBM_GBPS_CAP = 819.0  # v5e HBM bandwidth; implied reads above it are
                       # artifacts (the accounting already undercounts by
                       # excluding KV-cache traffic)


def _plausible(e: dict) -> bool:
    t = e.get("achieved_model_tflops",
              e.get("achieved_model_tflops_active"))
    if t is not None and t > _TFLOPS_CAP:
        return False
    bw = e.get("implied_weight_read_gb_per_sec")
    return bw is None or bw <= _HBM_GBPS_CAP


def _better(new: dict, old: dict) -> dict:
    """Best-of-recordings per metric: keep the best number ever recorded
    (and never replace a valid recording with an error entry or a
    faster-than-the-hardware artifact).  A ratchet cannot show a
    regression — ROADMAP Speed item 1 replaces it."""
    if "error" in new:
        return old
    if "error" in old:
        return new
    if "value" in new and "value" in old:
        if not _plausible(new):
            return old if _plausible(old) else {**new,
                                                "contention_artifact": True}
        if not _plausible(old):
            return new
        best = new if new["value"] >= old["value"] else old
        # side-measurements recorded once (e.g. the decode row's
        # batch-scaling sweep) survive a ratchet replacement that did not
        # re-measure them
        for extra_key in ("throughput_scaling", "reference_batch_recording",
                          "linear_only_recording", "remat_on_recording",
                          "speedup_vs_bf16_batch1",
                          "int8_embedding_table_ab", "accounting_note",
                          "weight_read_mb_per_token", "weight_total_mb",
                          "same_window_vs_dense_lm"):
            if extra_key not in best:
                loser = old if best is new else new
                if extra_key in loser:
                    best = {**best, extra_key: loser[extra_key]}
        return best
    if new.get("metric") == "flash_attention_causal_bf16":
        # per-row ratchet on the flash fwd+bwd TFLOPs, with a plausibility
        # gate: a row whose fwd+bwd measured faster than fwd alone is a
        # contention artifact and must not be locked in as "best"
        def plausible(row):
            f = row.get("flash", {})
            return f.get("fwd_bwd_ms", 0) >= 0.9 * f.get("fwd_ms", 0)

        def tflops(row):
            return row.get("flash", {}).get("fwd_bwd_tflops", 0)

        rows = []
        old_rows = {r.get("seq_len"): r for r in old.get("rows", [])}
        for r in new.get("rows", []):
            o = old_rows.get(r.get("seq_len"))
            if o is None:
                # first recording for this seq_len: an implausible row
                # (fwd_bwd faster than fwd) is a contention artifact —
                # record it, but marked so it never reads as a "best"
                # and a later plausible row always replaces it
                rows.append(r if plausible(r)
                            else {**r, "contention_artifact": True})
            elif plausible(r) and (tflops(r) >= tflops(o)
                                   or not plausible(o)):
                rows.append(r)
            else:
                rows.append(o)
        # best-ever rows for seq_lens the new run did not measure survive
        new_seqs = {r.get("seq_len") for r in new.get("rows", [])}
        rows += [o for s, o in old_rows.items() if s not in new_seqs]
        merged = dict(new)
        merged["rows"] = rows
        return merged
    key = {
        # a fed pipeline beats any starved one, then rank by step rate
        "imagenet_input_pipeline_vs_resnet50_step":
            lambda e: (bool(e.get("loader_keeps_chip_fed")),
                       e.get("resnet50_bf16_step_images_per_sec", 0)),
    }.get(new.get("metric"))
    if key is not None:
        best = new if key(new) >= key(old) else old
        if new.get("metric") == "imagenet_input_pipeline_vs_resnet50_step":
            # the winning row may come from a contended window: carry the
            # best ResNet-50 step rate ever measured so the chip-rate
            # evidence survives the fed-first ranking
            best = dict(best)
            best["best_step_images_per_sec_ever"] = max(
                e.get(k, 0) or 0
                for e in (new, old)
                for k in ("resnet50_bf16_step_images_per_sec",
                          "best_step_images_per_sec_ever"))
        return best
    return new


def main() -> None:
    sys.path.insert(0, _REPO)
    from benchmarks import (attention, bench_mesh_rules, bench_pipeline,
                            bench_roles, bench_serve, generate,
                            imagenet_e2e, input_pipeline, moe_lm,
                            resnet_cifar, scaling, transformer_lm,
                            vit_train)

    out = os.path.join(_REPO, "BENCH_EXTENDED.json")
    previous = {}
    if os.path.exists(out):
        try:
            with open(out) as f:
                previous = {e.get("metric"): e for e in json.load(f)}
        except (ValueError, KeyError):
            pass

    metric_names = {
        "mnist": "mnist_convnet_train_images_per_sec_per_chip",
        "resnet_cifar": "resnet18_cifar10_bf16_train_images_per_sec_per_chip",
        "scaling": "ddp_weak_scaling_overhead_virtual_cpu_mesh",
        "input_pipeline": "imagenet_input_pipeline_vs_resnet50_step",
        "attention": "flash_attention_causal_bf16",
        "transformer_lm": "transformer_lm_bf16_train_tokens_per_sec_per_chip",
        "moe_lm": "transformer_moe_lm_bf16_train_tokens_per_sec_per_chip",
        "lm_long": "transformer_lm_long_context_8k_bf16_tokens_per_sec_per_chip",
        "lm_32k": "transformer_lm_long_context_32k_bf16_tokens_per_sec_per_chip",
        "imagenet_e2e": "resnet50_imagenet_e2e_sustained_images_per_sec",
        "vit_train": "vit_b16_imagenet_bf16_train_images_per_sec_per_chip",
        "generate": "transformer_lm_decode_tokens_per_sec",
        "prefill": "transformer_lm_prefill_tokens_per_sec",
        "generate_int8": "transformer_lm_decode_int8_tokens_per_sec",
        "gen_latency": "transformer_lm_decode_batch1_tokens_per_sec",
        "gen_latency_int8": "transformer_lm_decode_batch1_int8_tokens_per_sec",
        "gen_long_int8_cache": "transformer_lm_decode_long_context_int8_cache",
        "serve": "serve_continuous_batching_tokens_per_sec",
        "serve_sharded": "serve_sharded_tokens_per_sec",
        "serve_disagg": "serve_disagg_tokens_per_sec",
        "roles": "roles_channel_dp_best_mb_s",
        "pipeline": "pipeline_host_tokens_per_sec",
        "mesh_rules": "mesh_rules_dp_tp_wire_reduction_world4",
    }
    import bench  # repo-root headline (MNIST ConvNet) — ratchet a copy here
    results = []
    for name, fn in (("mnist", bench.run),
                     ("resnet_cifar", resnet_cifar.run),
                     ("scaling", scaling.run),
                     ("input_pipeline", input_pipeline.run),
                     ("attention", attention.run),
                     ("transformer_lm", transformer_lm.run),
                     ("moe_lm", moe_lm.run),
                     ("lm_long", transformer_lm.run_long),
                     ("lm_32k", transformer_lm.run_32k),
                     ("imagenet_e2e", imagenet_e2e.run),
                     ("vit_train", vit_train.run),
                     ("generate", generate.run),
                     ("prefill", generate.run_prefill),
                     ("generate_int8", generate.run_int8),
                     ("gen_latency", generate.run_latency),
                     ("gen_latency_int8", generate.run_latency_int8),
                     ("gen_long_int8_cache",
                      generate.run_long_context_int8_cache),
                     ("serve", bench_serve.run),
                     ("serve_sharded", bench_serve.run_sharded),
                     ("serve_disagg", bench_serve.run_disagg),
                     ("roles", bench_roles.run),
                     ("pipeline", bench_pipeline.run),
                     ("mesh_rules", bench_mesh_rules.run)):
        try:
            r = fn()
        except Exception as e:  # record the failure, keep the rest running
            r = {"metric": metric_names.get(name, name),
                 "error": repr(e)[:500]}
        old = previous.get(r.get("metric"))
        if old is not None:
            r = _better(r, old)
        elif not _plausible(r):
            r = {**r, "contention_artifact": True}
        print(json.dumps(r))
        results.append(r)

    # entries recorded by other tools (e.g. test_tier_timings) survive
    ours = {r.get("metric") for r in results}
    results += [e for m, e in previous.items() if m not in ours]

    with open(out, "w") as f:
        json.dump(results, f, indent=1)
    print(f"wrote {out}")


if __name__ == "__main__":
    main()
