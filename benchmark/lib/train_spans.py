"""What the readers of a patterned, expert-sharded train step share beside
arithmetic (``lib/flops_hybrid.py``): picking the flash kernels by name and
reading the program's ``train-moe`` spans out of a capture."""

from __future__ import annotations

import warnings
from typing import List

from benchmark.lib import spans

FLASH_KERNELS = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def is_flash(o) -> bool:
    """A flash-attention kernel execution (the step's other Pallas calls
    are the grouped-matmul kernels)."""
    return o.is_pallas and o.name.split(".")[0] in FLASH_KERNELS


def moe_spans(run) -> List[spans.Span]:
    """The ``train-moe`` spans (``step=`` ``assignments=`` ``held=``) of
    the run's capture; [] where there is no capture or the program emits
    none (a parent commit without the span)."""
    cached = getattr(run, "_train_moe_spans", None)
    if cached is None:
        if run.trace is None or not run.trace.path:
            return []
        from jax.profiler import ProfileData

        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            found = spans.from_profile(
                ProfileData.from_file(run.trace.path), {"train-moe"})
        cached = run._train_moe_spans = [
            s for s in found if "held" in s.args and "assignments" in s.args]
    return cached
