"""Device time of the decode-chunk program (the engine's jitted
``run_chunk``) in the traced round, per decode step it ran."""

PROGRAM = "run_chunk"


def read(r):
    t = r["trace"].program_s(PROGRAM)
    if not t or not r["decode_steps"]:
        return None
    return 1e3 * t / r["decode_steps"]
