"""Encoder networks for the codec, plus the seed's LM modules.

The codec uses :mod:`repro.models.block_attention` (the attention encoder
family, with its plain reference :mod:`repro.models.block_attention_ref`)
and the layers in :mod:`repro.models.common`. The package imports nothing
itself, so loading those modules loads no LM code.

The rest (``registry``, ``transformer``, ``whisper``, ``rglru``,
``rwkv6``) is the seed's LM stack: a leaf that nothing in the codec
imports, kept only for its own tests until it is deleted.
"""
