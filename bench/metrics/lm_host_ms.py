"""Mean host time per decode step of the engine's ``lm.fetch`` (the
logits' device→host copy) and ``lm.sample`` spans, in ms."""


def read(rec):
    spans = rec.get("spans") or []
    steps = sum(s["name"] == "serve.decode_step" for s in spans)
    host = sum(s["dur"] for s in spans if s["name"] in ("lm.fetch", "lm.sample"))
    return host / steps / 1e3 if steps else None
