"""Spans on the profiler's clock: the wall- and device-clock half of
``observe`` (``Recorder`` keeps the simulated Eq.-1 clock).

A span is a ``jax.profiler.TraceAnnotation``. Outside a trace it costs
one inactive ``TraceMe`` check; under ``jax.profiler`` it lands on the
profiler's host plane, on the clock of the device planes, and the
profiler keeps it until the trace stops. ``SPANS`` is the one table of
names: the benchmark's reduction (``bench/trace_reduce.py``) labels the
device's idle gaps by them.
"""
from __future__ import annotations

import functools

import jax

# name -> (the span it nests in, the layer it times)
SPANS = {
    "s2fl.round": (None, "engine: one S2FLEngine.run_round, step-numbered"),
    "s2fl.driver": ("s2fl.round",
                    "driver / scheduler: selection, Eq.-1 clock, events"),
    "s2fl.execute": ("s2fl.driver", "engine: the round's real work"),
    "s2fl.group": ("s2fl.execute", "balance: Eq.-2 grouping"),
    "s2fl.batch": ("s2fl.execute",
                   "engine data: host gather and upload of a batch"),
    "s2fl.client_fwd": ("s2fl.execute", "model step: client-half forward"),
    "s2fl.server_step": ("s2fl.execute",
                         "model step: a group's server half, loss read"),
    "s2fl.client_update": ("s2fl.execute",
                           "model step: client-half backward and SGD"),
    "s2fl.server_sgd": ("s2fl.execute",
                        "engine: eager SGD of a group's server copy"),
    "s2fl.uplink": ("s2fl.execute", "channel: features, device to server"),
    "s2fl.downlink": ("s2fl.execute",
                      "channel: feature gradients, server to device"),
    "s2fl.dispatch": ("s2fl.execute", "channel: Wc, server to device"),
    "s2fl.collect": ("s2fl.execute", "channel: trained Wc, device to server"),
    "s2fl.aggregate": ("s2fl.round", "aggregation: Algorithm 1"),
}


def _known(name: str) -> str:
    if name not in SPANS:
        raise KeyError(f"span {name!r} is not in observe.spans.SPANS")
    return name


def span(name: str, **args):
    """Context manager: the span ``name`` with ``args`` as its stats."""
    return jax.profiler.TraceAnnotation(_known(name), **args)


def traced(name: str):
    """Decorator: every call of the function inside the span ``name``."""
    return functools.partial(jax.profiler.annotate_function,
                             name=_known(name))


def round_span(n: int):
    """The round's step annotation, carrying its step number."""
    return jax.profiler.StepTraceAnnotation("s2fl.round", step_num=n)
