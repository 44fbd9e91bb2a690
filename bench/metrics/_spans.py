"""Readers of the program's own spans and scopes in a traced window
(``span_reduce``: ``span_idle``, ``span_count``, ``module_count``,
``scope_busy``; loading this module puts ``span_reduce`` in the traced
run's reduction). A program without them reads nothing: no
``s2fl.round`` span started in the window, or no operation carried a
scope.

``PARTS`` splits a CNN federation's idle time by the layer the host was
in (the innermost ``s2fl.*`` span of ``repro.observe.spans``); the host
part is every other ``s2fl.*`` span (driver, scheduler, balance,
channel, the round's own). With ``(outside)`` (the harness: its loop,
snapshot restores) the parts add up to ``idle_frac.cnn``."""
import span_reduce

span_reduce.install()

ROUND = "s2fl.round"
PARTS = {
    "batch": ("s2fl.batch",),
    "halves": ("s2fl.client_fwd", "s2fl.server_step", "s2fl.client_update"),
    "server_sgd": ("s2fl.server_sgd",),
    "aggregate": ("s2fl.aggregate",),
}


def spans_of(ctx, unit):
    """The reduced trace where the program's spans are in it, else None."""
    tr = ctx["trace"]
    if tr is None or ctx["unit"] != unit or \
            ROUND not in tr.get("span_count", {}):
        return None
    return tr


def idle_in(ctx, part):
    """Idle share of the window under the spans of ``PARTS[part]``."""
    tr = spans_of(ctx, "samples")
    if tr is None:
        return None
    return sum(tr["span_idle"].get(s, 0.0)
               for s in PARTS[part]) / tr["window_s"]


def idle_elsewhere(ctx):
    """Idle share of the window under every ``s2fl.*`` span not in
    ``PARTS``."""
    tr = spans_of(ctx, "samples")
    if tr is None:
        return None
    named = {s for spans in PARTS.values() for s in spans}
    return sum(v for k, v in tr["span_idle"].items()
               if k.startswith(span_reduce.SPAN_PREFIX)
               and k not in named) / tr["window_s"]


def per_round(ctx, unit):
    """Device programs the first chip started per round started."""
    tr = spans_of(ctx, unit)
    if tr is None:
        return None
    return tr["module_count"] / tr["span_count"][ROUND]


def device_share(ctx, scope):
    """Share of a language model's busy device time in ``scope``."""
    tr = ctx["trace"]
    if tr is None or ctx["unit"] != "tokens" or not tr.get("scope_busy"):
        return None
    return tr["scope_busy"].get(scope, 0.0) / tr["busy_s"]
