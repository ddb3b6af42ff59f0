"""Share of decode rows that produced a kept token: decode tokens over
(decode steps x slots), from the engine's counts over the whole window.
Layer: serve engine (``launch/serve.py``).  Moves ``tok_s``."""


def read(r):
    steps = r.counts["decode_steps"]
    if not steps:
        return None
    return 100.0 * r.counts["decode_tokens"] / (steps * r.counts["slots"])
