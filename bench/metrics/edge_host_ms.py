"""Mean host time per batch of the service's ``edge.pad`` and ``edge.crop``
spans (``repro.obs.trace``), in ms."""


def read(rec):
    spans = rec.get("spans") or []
    pad = [s["dur"] for s in spans if s["name"] == "edge.pad"]
    crop = [s["dur"] for s in spans if s["name"] == "edge.crop"]
    if not pad:
        return None
    return (sum(pad) + sum(crop)) / len(pad) / 1e3
