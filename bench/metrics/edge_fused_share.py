"""Share of the window's flushed batches that the fused conv kernel served,
in %: the batches whose ``edge.execute`` (or ``edge.compile``) span says
``conv="fused"``, one span a batch, as the service's
``serving_edge_fused_batches_total`` counts them. None when no span names
its conv path (a program that does not record it)."""


def read(rec):
    paths = [s["args"]["conv"] for s in rec.get("spans") or []
             if s["name"] in ("edge.execute", "edge.compile")
             and "conv" in s.get("args", {})]
    if not paths:
        return None
    return 100.0 * paths.count("fused") / len(paths)
