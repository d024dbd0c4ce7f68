"""Entropy and container layer: mean seconds per job in the container
encode (the program's ``gbatc.container.encode`` span around
``codec/encode.py::encode``: Huffman on the latents, guarantee stream
packing, integrity CRCs)."""

from bench import stages


def read(ctx):
    return stages.seconds_per_job(ctx, "gbatc.container.encode")
