"""Container layer: percent of the containers' bytes that are the
decoder's parameter stream (the ``decoder_bytes`` and ``bytes`` stats of
the program's ``gbatc.container.encode`` span, summed over the window's
jobs). A decoder stored in every container is bytes the compression
ratio pays for."""

from bench import stages


def read(ctx):
    found = stages.spans(ctx)
    if not found:
        return None
    hits = [s.stats for s in found if s.name == "gbatc.container.encode"
            and s.stats.get("bytes")]
    if not hits:
        return None
    return 100.0 * sum(h.get("decoder_bytes", 0) for h in hits) / sum(
        h["bytes"] for h in hits)
