"""repro: GBATC (guaranteed block autoencoder with tensor correlations), a
lossy codec with a hard error bound for multi-species simulation fields,
in JAX.

Layers:
  repro.codec     — public codec API: bytes-in/bytes-out GBATC container
                    (fit/compress to a self-describing blob, standalone and
                    selective decompress with no fitted state), encoder
                    family registry, integrity and salvage
  repro.core      — the paper's contribution (GBA / GBATC / guarantee
                    engine / entropy coding / SZ baseline)
  repro.nn        — minimal functional module system (params as pytrees)
  repro.models    — the block-attention encoder family and shared layers
  repro.train     — optimizer, minibatch trainer, fault tolerance
  repro.parallel  — mesh-sharded fit and guarantee engine, gradient
                    compression
  repro.serve     — continuous-batched decode service over containers
  repro.kernels   — Pallas TPU kernels (+ pure-jnp oracles)
  repro.data      — synthetic S3D surrogate
  repro.analysis  — invariant checker (lint, wire schema, jaxpr audit)
  repro.tracing   — stage spans for the profiler

The seed's LM modules (``repro.configs``, the LM architectures in
``repro.models``, ``repro.data.tokens``, ``repro.serve.serve_loop`` and
``kvcache``, ``repro.launch``, the RWKV6 / RG-LRU / block-quant kernels)
remain as a leaf that nothing in the codec imports; it is due for
deletion (ROADMAP D2).
"""

__version__ = "1.0.0"
