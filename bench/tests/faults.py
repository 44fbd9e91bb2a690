"""Faults planted underneath the timed path, for the tests that see the
benchmark's check come out false: the program itself is broken (through
``monkeypatch``), never the check."""
from __future__ import annotations

import jax


def _half_rows(x):
    return x[:max(1, x.shape[0] // 2)]


def _half_server_batch(model, feats, batch):
    """The server's share of the batch with half of it left out: half the
    rows of a CNN batch, half the tokens of each LM sequence."""
    if model.is_cnn:
        return dict(feats, h=_half_rows(feats["h"])), \
            dict(batch, y=_half_rows(batch["y"]))
    labels = batch["labels"]
    return feats, dict(batch, labels=labels.at[..., labels.shape[-1] // 2:]
                       .set(-100))


def plant(fault: str, monkeypatch):
    """``unchanged``: the step returns its state unchanged.
    ``half_batch``: half of the batch is left out, the loss the mean over
    the rest. ``server_grad_half``: the server's gradient comes from half
    of the batch, its loss value from all of it."""
    from repro.core import engine, round_step
    from repro.models.api import SplitModel
    orig_loss = SplitModel.server_loss
    if fault == "unchanged":
        orig_step = round_step.make_s2fl_train_step

        def make_step(*a, **k):
            step = orig_step(*a, **k)

            def broken(params, batch):
                return params, step(params, batch)[1]
            return broken
        monkeypatch.setattr(round_step, "make_s2fl_train_step", make_step)
        monkeypatch.setattr(engine.S2FLEngine, "_commit",
                            lambda self, gids: [self._held.pop(g)
                                                for g in gids])
    elif fault == "half_batch":
        def half(self, params, feats, batch, split, train=True):
            return orig_loss(self, params,
                             *_half_server_batch(self, feats, batch), split,
                             train)
        monkeypatch.setattr(SplitModel, "server_loss", half)
        orig_sample = engine.S2FLEngine._sample_batch
        monkeypatch.setattr(
            engine.S2FLEngine, "_sample_batch",
            lambda self, cid: {k: _half_rows(v) for k, v in
                               orig_sample(self, cid).items()})
    elif fault == "server_grad_half":
        def grad_half(self, params, feats, batch, split, train=True):
            full, met = orig_loss(self, params, feats, batch, split, train)
            part, _ = orig_loss(self, params,
                                *_half_server_batch(self, feats, batch),
                                split, train)
            sg = jax.lax.stop_gradient
            return sg(full) + part - sg(part), met
        monkeypatch.setattr(SplitModel, "server_loss", grad_half)
    else:
        raise ValueError(fault)
