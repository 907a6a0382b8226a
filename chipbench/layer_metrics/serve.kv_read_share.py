"""Share of the K/V pool's time blocks that the decode steps of the window
read (``SlotEngine.stats()["decode_attn"]``: blocks the busy slots held over
blocks of the whole pool).  Where the decode program runs on the dense branch
it reads the whole pool whatever the slots hold: 100.  A program without the
counter, as the parent of PR 26 is, reports nothing."""


def read(run):
    attn = run.counters.get("engine", {}).get("decode_attn")
    if not attn or not attn["kv_blocks_pool"]:
        return None
    if not attn["kernel"]:
        return 100.0
    return 100.0 * attn["kv_blocks_read"] / attn["kv_blocks_pool"]
