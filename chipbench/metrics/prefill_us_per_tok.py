"""Device time of the admission programs (``_admit_impl``) per valid prompt
token admitted while traced, in us: padding to buckets counts as cost, not
as tokens.  Layer: model step.  Moves ``ttft_p90_ms``."""


def read(r):
    m = r.trace.module("_admit_impl")
    tokens = sum(r.admitted)
    if m is None or not tokens:
        return None
    return m[0] / tokens * 1e6
