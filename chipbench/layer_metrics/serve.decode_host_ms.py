"""The host's own milliseconds in one decode iteration, outside its wait on
the device: (sum of ``decode.dispatch`` + sum of ``decode.emit``) over the
iterations of the window.  ``decode.emit`` holds the per-slot bookkeeping and
every token frame the frontend sends from the decode thread."""

from chipbench import phases

HOST = ("decode.dispatch", "decode.emit")


def read(run):
    p = phases.engine(run)
    if not p or not p["decode.dispatch"]["count"]:
        return None
    return 1e3 * phases.seconds(p, HOST) / p["decode.dispatch"]["count"]
