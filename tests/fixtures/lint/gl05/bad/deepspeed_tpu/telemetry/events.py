"""The fixture registry GL05 resolves (pure AST, never imported)."""

KINDS = ("compile", "serving", "fault", "span", "gateway")


def make_event(kind, name, step, rank, data):
    return {"kind": kind, "name": name, "step": step, "rank": rank,
            "data": data}


SPANS = ("request", "queue", "decode", "draft", "verify",
         "spec_commit", "migrate", "gateway", "ingress", "quota",
         "startup", "startup.pool", "startup.serving_init")
