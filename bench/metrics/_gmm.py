"""The held experts' grouped-matmul kernels (``held_experts_gmm`` and
``held_experts_tgmm``) in a traced window.

Their calls, with each call's seconds, are what ``trace_reduce`` finds
under the HLO instruction-name prefix ``KERNEL``. Only
``device_frac.moe.gmm`` names it as its ``KERNEL``: ``trace_reduce``
gives a call once to each reader that names a prefix it matches, so two
readers naming it would each read every call twice, and
``roofline.moe.gmm`` reads the calls that reader asked for. Both are
listed for the same cells.

The least time of those calls comes from the path that built the
program: at set-up it publishes one step's calls with each call's FLOPs
and HBM bytes, and the work of a step (``publish``). A run of a program
without the kernels finds no call and publishes nothing, and the readers
read nothing."""

KERNEL = "held_experts_"

_step = {}


def publish(work_per_step: float, calls: list) -> None:
    """One step's kernel calls, [(calls, FLOPs of one, HBM bytes of
    one), ...], and the units of work (``ctx['work']``) a step does."""
    _step.clear()
    _step.update(work=work_per_step, calls=list(calls))


def published() -> dict | None:
    return dict(_step) or None


def calls(ctx):
    tr = ctx["trace"]
    if tr is None or ctx["unit"] != "tokens":
        return None
    return tr["kernel_calls"].get(KERNEL) or None
