"""Share of decode-slot steps that produced a token: tokens that decode
steps produced (every token but each request's first, which its prefill
produced) over decode steps times decode slots, between GO and the end of
the drain. In %."""


def read(spec: dict, facts: dict):
    steps = facts.get("decode_steps")
    if not steps:
        return None
    return 100.0 * facts["decode_tokens"] / (steps * facts["decode_slots"])
