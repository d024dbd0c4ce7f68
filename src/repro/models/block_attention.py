"""Patch-token block attention encoder/decoder (the second GBATC family).

The paper group's follow-up (arxiv 2409.05357) replaces the conv block
autoencoder with attention for better rate at the same bound; this module
is the repository's own encoder/decoder pair after that idea, not the
paper's architecture, over the *same* block instances the conv AE uses:
an (NB, S, bt, ph, pw) block flattens to ``S * bt`` patch tokens of
dimension ``ph * pw`` (one token per species per frame of the block), a
dense projection + sinusoidal positions lifts them to ``d_model``,
``depth`` pre-norm non-causal transformer blocks (multi-head attention +
SwiGLU FFN, the :mod:`repro.models.transformer` idioms) mix them, and one
FC maps the flattened token grid to the shared 36-dim latent. The decoder
mirrors exactly (its own projection, blocks, and un-embed), so the codec
stores decoder-side parameters only, like the conv family.

Everything downstream is family-agnostic: ``fit`` trains through the same
compiled :class:`repro.train.train_loop.MiniBatchTrainer`, the unchanged
``GuaranteeEngine`` bounds whatever this decoder reconstructs, and the
fused decode builder in :mod:`repro.codec.families` consumes the same
``decode(params, z) -> (NB, S, bt, ph, pw)`` contract. ``attn_impl``
selects the attention path: ``"direct"`` (default) runs
:func:`repro.models.common.attention`; ``"flash"`` routes through the
Pallas :func:`repro.kernels.flash_attention.flash_attention` kernel
(interpret mode off-TPU), retained bit-comparable for accelerator runs.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.models import common
from repro.nn.module import Param, init_tree
from repro.train import train_loop


@dataclasses.dataclass(frozen=True)
class BlockAttentionConfig:
    n_species: int
    block: tuple[int, int, int]  # (bt, ph, pw)
    latent: int = 36
    d_model: int = 32
    n_heads: int = 2
    depth: int = 1
    mlp_hidden: int = 64
    dtype: Any = jnp.float32
    attn_impl: str = "direct"  # "direct" | "flash" (Pallas kernel)

    def __post_init__(self):
        if self.d_model % self.n_heads:
            raise ValueError(
                f"d_model {self.d_model} not divisible by n_heads "
                f"{self.n_heads}"
            )

    @property
    def n_tokens(self) -> int:
        return self.n_species * self.block[0]

    @property
    def token_dim(self) -> int:
        return self.block[1] * self.block[2]

    @property
    def arch(self) -> tuple[int, int, int, int]:
        """The wire arch words (see ``codec.families``): the four u16
        fields that, with geometry/latent, fully rebuild this config."""
        return (self.d_model, self.n_heads, self.depth, self.mlp_hidden)


def _attn_defs(cfg: BlockAttentionConfig):
    dm, dt = cfg.d_model, cfg.dtype
    return {
        "wq": Param((dm, dm), dt, "fan_in", ("embed", "heads")),
        "wk": Param((dm, dm), dt, "fan_in", ("embed", "heads")),
        "wv": Param((dm, dm), dt, "fan_in", ("embed", "heads")),
        "wo": Param((dm, dm), dt, "fan_in", ("heads", "embed")),
    }


def _ffn_defs(cfg: BlockAttentionConfig):
    dm, df, dt = cfg.d_model, cfg.mlp_hidden, cfg.dtype
    return {
        "wg": Param((dm, df), dt, "fan_in", ("embed", "mlp")),
        "wu": Param((dm, df), dt, "fan_in", ("embed", "mlp")),
        "wd": Param((df, dm), dt, "fan_in", ("mlp", "embed")),
    }


def _norm_defs(cfg: BlockAttentionConfig):
    return {"scale": Param((cfg.d_model,), jnp.float32, "ones", (None,))}


def _block_defs(cfg: BlockAttentionConfig):
    return {
        "ln1": _norm_defs(cfg),
        "attn": _attn_defs(cfg),
        "ln2": _norm_defs(cfg),
        "ffn": _ffn_defs(cfg),
    }


def _rms_norm(p, x, eps: float = 1e-6):
    x32 = x.astype(jnp.float32)
    ms = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return (x32 * jax.lax.rsqrt(ms + eps) * p["scale"]).astype(x.dtype)


class BlockAttentionAE:
    """Encoder/decoder over (NB, S, bt, ph, pw) blocks; same contract as
    :class:`repro.core.autoencoder.BlockAutoencoder` (``encode``,
    ``decode``, ``defs`` with ``enc``/``dec`` key prefixes, ``init``)."""

    def __init__(self, cfg: BlockAttentionConfig):
        self.cfg = cfg
        # fixed (not learned) positions: the token grid is static per
        # structural config, so they need no bytes on the wire
        self._pos = jnp.asarray(
            common.sinusoidal_positions(cfg.n_tokens, cfg.d_model)
        )
        self._trainers: dict[tuple, train_loop.MiniBatchTrainer] = {}

    # ---- definition tree ------------------------------------------------
    @property
    def defs(self):
        cfg = self.cfg
        dm, td, nt = cfg.d_model, cfg.token_dim, cfg.n_tokens
        d: dict = {
            "enc_proj": {"w": Param((td, dm), cfg.dtype, "fan_in"),
                         "b": Param((dm,), cfg.dtype, "zeros")},
            "enc_head": {"w": Param((nt * dm, cfg.latent), cfg.dtype,
                                    "fan_in"),
                         "b": Param((cfg.latent,), cfg.dtype, "zeros")},
            "enc_norm": _norm_defs(cfg),
            "dec_proj": {"w": Param((cfg.latent, nt * dm), cfg.dtype,
                                    "fan_in"),
                         "b": Param((nt * dm,), cfg.dtype, "zeros")},
            "dec_head": {"w": Param((dm, td), cfg.dtype, "fan_in"),
                         "b": Param((td,), cfg.dtype, "zeros")},
            "dec_norm": _norm_defs(cfg),
        }
        for i in range(cfg.depth):
            d[f"enc_block{i}"] = _block_defs(cfg)
            d[f"dec_block{i}"] = _block_defs(cfg)
        return d

    def init(self, key):
        return init_tree(self.defs, key)

    # ---- forward ---------------------------------------------------------
    def _tokens(self, x):
        # (NB, S, bt, ph, pw) -> (NB, S*bt, ph*pw) patch tokens
        nb = x.shape[0]
        return x.reshape(nb, self.cfg.n_tokens, self.cfg.token_dim)

    def _attention(self, p, x):
        cfg = self.cfg
        b, t, _ = x.shape
        hd = cfg.d_model // cfg.n_heads
        q = (x @ p["wq"]).reshape(b, t, cfg.n_heads, hd)
        k = (x @ p["wk"]).reshape(b, t, cfg.n_heads, hd)
        v = (x @ p["wv"]).reshape(b, t, cfg.n_heads, hd)
        if cfg.attn_impl == "flash":
            from repro.kernels import flash_attention as fa

            o = fa.flash_attention(
                q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                v.transpose(0, 2, 1, 3), causal=False,
                interpret=jax.default_backend() != "tpu",
            ).transpose(0, 2, 1, 3)
        else:
            o = common.attention(q, k, v, causal=False)
        return o.reshape(b, t, -1) @ p["wo"]

    def _block(self, p, x):
        x = x + self._attention(p["attn"], _rms_norm(p["ln1"], x))
        h = _rms_norm(p["ln2"], x)
        return x + (jax.nn.silu(h @ p["ffn"]["wg"])
                    * (h @ p["ffn"]["wu"])) @ p["ffn"]["wd"]

    def encode(self, params, x):
        cfg = self.cfg
        h = self._tokens(x) @ params["enc_proj"]["w"] \
            + params["enc_proj"]["b"] + self._pos
        for i in range(cfg.depth):
            h = self._block(params[f"enc_block{i}"], h)
        h = _rms_norm(params["enc_norm"], h)
        h = h.reshape(h.shape[0], -1)
        return h @ params["enc_head"]["w"] + params["enc_head"]["b"]

    def decode(self, params, z):
        cfg = self.cfg
        s, (bt, ph, pw) = cfg.n_species, cfg.block
        h = z @ params["dec_proj"]["w"] + params["dec_proj"]["b"]
        h = h.reshape(-1, cfg.n_tokens, cfg.d_model) + self._pos
        for i in range(cfg.depth):
            h = self._block(params[f"dec_block{i}"], h)
        h = _rms_norm(params["dec_norm"], h)
        h = h @ params["dec_head"]["w"] + params["dec_head"]["b"]
        return h.reshape(-1, s, bt, ph, pw)

    def __call__(self, params, x):
        return self.decode(params, self.encode(params, x))

    def decoder_param_bytes(self, params) -> int:
        dec = {k: v for k, v in params.items() if k.startswith("dec")}
        return sum(l.size * l.dtype.itemsize for l in jax.tree.leaves(dec))


def _loss(model: BlockAttentionAE):
    def loss_fn(p, batch):
        rec = model(p, batch)
        return jnp.mean(jnp.square(rec - batch))

    return loss_fn


def fit(
    model: BlockAttentionAE,
    blocks: np.ndarray,
    *,
    steps: int = 400,
    batch_size: int = 64,
    lr: float = 1e-3,
    seed: int = 0,
    log_every: int = 0,
    mode: Optional[str] = None,
    mesh=None,
) -> tuple[Any, np.ndarray]:
    """Train with AdamW on MSE through the compiled mini-batch engine —
    the exact :func:`repro.core.autoencoder.fit` contract, so the
    pipeline's family handle can call either interchangeably. Returns
    (params, loss_history); the engine is cached on the model, so
    refitting never re-traces."""
    params = model.init(jax.random.PRNGKey(seed))
    key = (lr, steps, mode)
    trainer = model._trainers.get(key)
    if trainer is None:
        trainer = train_loop.MiniBatchTrainer(
            _loss(model),
            train_loop.adamw_cfg(lr, steps),
            mode=mode,
            log_fn=lambda t, loss: print(f"[attn] step {t} loss {loss:.3e}"),
        )
        model._trainers[key] = trainer
    return trainer.fit(
        params, (blocks,), steps=steps, batch_size=batch_size, seed=seed,
        log_every=log_every, mesh=mesh,
    )
