"""Share of the window's whole-prompt prefills whose program computes its
attention with the causal flash forward kernel rather than over materialised
scores (``SlotEngine.stats()["prefill_attn"]``: ``kernel_prefills`` /
``prefills``; a latent layer's ``takes_prefill_kernel`` decides by the call).
Prints what those prefills executed over what their true prompt lengths
needed, in (query, key) pairs: a bucket's padding, the kernel's sub-tiles
above the diagonal's edge and, on the dense branch, the whole square.  A
program without the counter, as the parent of PR 39 is, and a window without
a prefill report nothing."""


def read(run):
    attn = run.counters.get("engine", {}).get("prefill_attn")
    if not attn or not attn.get("prefills"):
        return None
    if attn.get("pairs_needed"):
        print(f"[chipbench]     prefill attention: {attn['prefills']} "
              f"prefills, {attn['kernel_prefills']} on the kernel; pairs "
              f"executed / needed {attn['pairs_executed']} / "
              f"{attn['pairs_needed']} = "
              f"{attn['pairs_executed'] / attn['pairs_needed']:.4f}",
              flush=True)
    return 100.0 * attn["kernel_prefills"] / attn["prefills"]
