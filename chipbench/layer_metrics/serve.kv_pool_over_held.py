"""How many times the K/V columns the busy slots HELD the window's decode
steps read: ``SlotEngine.stats()["decode_attn"]``'s ``kv_blocks_pool`` (the
time blocks of the whole pool, every slot at ``max_len``, summed over the
decode steps) over ``kv_blocks_read`` (the blocks the busy slots held) where
the step's attention is the dense branch, which reads the pool whole
(``kernel`` false: grouped queries, every CPU run); 1.0 where it is the
Pallas decode-attention kernel, which reads the held blocks alone.  The floor
under what a grouped-query slot-decode kernel could win: at 1.0 there is
nothing left.  Host arithmetic of the program, no device read.  A program
without the counter, or a window without a decode step, reports nothing."""


def read(run):
    attn = run.counters.get("engine", {}).get("decode_attn")
    if not attn or not attn.get("kv_blocks_read"):
        return None
    if attn.get("kernel"):
        return 1.0
    return attn["kv_blocks_pool"] / attn["kv_blocks_read"]
