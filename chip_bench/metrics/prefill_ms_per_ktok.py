"""Device time of the span-prefill programs (the engine's jitted
``prefill_span``) in the traced round, per 1,000 prompt tokens
prefilled."""

PROGRAM = "prefill_span"


def read(r):
    t = r["trace"].program_s(PROGRAM)
    if not t or not r["prompt_tokens"]:
        return None
    return 1e3 * t / (r["prompt_tokens"] / 1e3)
