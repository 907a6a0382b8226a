"""How late the load generator sent: 95th percentile of (sent - due), on the
generator's own clock.  Moves nothing; a value that is not small against the
latencies beside it voids them (a starved generator reads as a fast server)."""


def read(run):
    return run.client["late_ms_p95"] if run.client else None
