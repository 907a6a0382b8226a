"""Of the bytes the window's decode steps had to read by the mathematics
(``SlotEngine.stats()["decode_need"]``), the share that is cache, the resident
latent columns of the busy slots, and not weights: whether the pool or the
weights set a step's pace, the number a latent cache's small positions exist
to move.  Host arithmetic of the program, no device read.  A program without
the counter, as the parent of PR 32 is, reports nothing."""

from chipbench import decode_need


def read(run):
    return decode_need.latent_read_share(
        run.counters.get("engine", {}).get("decode_need"))
