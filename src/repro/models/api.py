"""SplitModel — the uniform protocol the S²FL core consumes.

A model is a sequence of *units* (transformer blocks or CNN units) plus an
input stem (embedding) and an output head. A split index ``s`` places
``stem + units[:s]`` on the client and ``units[s:] + head`` on the server;
the tensor crossing the cut is the paper's intermediate feature ``fx``.

Both forward halves take the FULL parameter pytree (grads for the other
half come back as zeros) — portion sizes / upload costs are accounted by
``repro.utils.flops`` from the segment map, and Algorithm-1 aggregation
operates on segments.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import CNNConfig
from repro.models import cnn as cnn_mod
from repro.models import transformer as tf_mod
from repro.models.params import abstract_params, init_params


class SplitModel:
    def __init__(self, cfg):
        self.cfg = cfg
        self.is_cnn = isinstance(cfg, CNNConfig) or cfg.arch_type == "cnn"

    # -- parameters ---------------------------------------------------------
    def defs(self):
        return (cnn_mod.cnn_defs(self.cfg) if self.is_cnn
                else tf_mod.model_defs(self.cfg))

    def init(self, key):
        return init_params(self.defs(), key, self.cfg.param_dtype)

    def abstract(self):
        return abstract_params(self.defs(), self.cfg.param_dtype)

    # -- structure ----------------------------------------------------------
    @property
    def n_units(self) -> int:
        return (cnn_mod.cnn_n_units(self.cfg) if self.is_cnn
                else self.cfg.n_layers)

    def segments(self):
        """Ordered (name, path) segment map over the param pytree.
        Paths index into the params dict."""
        segs = []
        if self.is_cnn:
            for i in range(self.n_units):
                segs.append((f"unit:{i}", ("units", i)))
            segs.append(("head", ("head",)))
            return segs
        segs.append(("embed", ("embed",)))
        for i in range(self.cfg.n_layers):
            segs.append((f"block:{i}", ("blocks", i)))
        d = self.defs()
        if "shared_attn" in d:
            segs.append(("shared_attn", ("shared_attn",)))
        segs.append(("final_norm", ("final_norm",)))
        if "head" in d:
            segs.append(("head", ("head",)))
        return segs

    def client_segments(self, split: int):
        """Segment names trained on the client for split s."""
        names = set()
        if self.is_cnn:
            names.update(f"unit:{i}" for i in range(split))
            return names
        names.add("embed")
        names.update(f"block:{i}" for i in range(split))
        if any(self.cfg.pattern()[i][0] == "shared_attn"
               for i in range(split)):
            names.add("shared_attn")
        return names

    # -- forward halves -----------------------------------------------------
    def client_forward(self, params, batch, split: int, train: bool = True):
        """Returns features dict {'h': ..., 'aux': scalar}."""
        if self.is_cnn:
            h = cnn_mod.cnn_apply_range(self.cfg, params, batch["x"], 0,
                                        split)
            return {"h": h, "aux": jnp.zeros((), jnp.float32)}
        h = tf_mod.apply_embed(self.cfg, params, batch["tokens"],
                               batch.get("prefix"))
        positions = jnp.arange(h.shape[1], dtype=jnp.int32)
        h, _, aux = tf_mod.apply_blocks(self.cfg, params, h, 0, split,
                                        positions, train=train)
        return {"h": h, "aux": aux}

    def server_loss(self, params, feats, batch, split: int,
                    train: bool = True):
        """CE(+aux) from the cut to the loss. Returns (loss, metrics)."""
        if self.is_cnn:
            h = cnn_mod.cnn_apply_range(self.cfg, params, feats["h"], split,
                                        self.n_units)
            with jax.named_scope("head"):
                logits = cnn_mod.cnn_head(self.cfg, params, h)
                onehot = jax.nn.one_hot(batch["y"], self.cfg.n_classes)
                ce = -jnp.mean(jnp.sum(onehot * jax.nn.log_softmax(logits),
                                       -1))
                acc = jnp.mean((jnp.argmax(logits, -1) == batch["y"])
                               .astype(jnp.float32))
            return ce + feats["aux"], {"ce": ce, "acc": acc}
        h = feats["h"]
        positions = jnp.arange(h.shape[1], dtype=jnp.int32)
        h, _, aux = tf_mod.apply_blocks(self.cfg, params, h, split,
                                        self.cfg.n_layers, positions,
                                        train=train)
        from repro.models.layers import cross_entropy
        with jax.named_scope("head"):
            logits = tf_mod.apply_head(self.cfg, params, h)
            P = logits.shape[1] - batch["tokens"].shape[1]
            if P:
                logits = logits[:, P:]
            ce = cross_entropy(logits, batch["labels"], self.cfg.vocab_size)
        loss = ce + aux + feats["aux"]
        return loss, {"ce": ce, "aux": aux + feats["aux"]}

    def full_loss(self, params, batch, train: bool = True):
        """Monolithic loss (FedAvg baseline / sanity oracle)."""
        if self.is_cnn:
            return cnn_mod.cnn_loss(self.cfg, params, batch)
        return tf_mod.lm_loss(self.cfg, params, batch, train=train)

    # -- inference (LM only) -------------------------------------------------
    def prefill(self, params, tokens, max_len, prefix=None):
        return tf_mod.prefill(self.cfg, params, tokens, max_len, prefix)

    def decode_step(self, params, token, caches, index):
        return tf_mod.decode_step(self.cfg, params, token, caches, index)


def get_subtree(params, path):
    node = params
    for p in path:
        node = node[p]
    return node
