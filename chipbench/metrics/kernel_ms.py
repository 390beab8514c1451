"""Device time of one Pallas kernel per step (training) or tick (serving):
the self time of the traced window's Mosaic operations (``tpu_custom_call``)
named for the kernel, over the steps or ticks in the window (``pallas_ms``'s
denominators), in ms. The kernel is the part of the metric's name between
its first and last dot; an operation counts when its name, less the ``%``
and the labels of the transformations around the call, starts with it
(``kernel_ms.paged_attn.itl`` reads ``%paged_attn_split.11`` and
``%paged_attn_combine.11``). An operation carries its ``pallas_call``'s
``name=``, wrapped in the transformations it was traced under: in the
training step's head ``%jvp_fused_kron_ce_fwd_.11`` and
``%transpose_jvp_fused_kron_ce_bwd__.11``. A program whose kernels carry no
such name gives no value. Source: the device trace."""

import re

TRANSFORMS = re.compile(r"^(?:(?:jvp|transpose|vmap)_)+")


def read(r, metric):
    n = r.counters.get("steps") or r.counters.get("ticks")
    if r.trace is None or not n:
        return None
    kernel = metric["name"].split(".", 1)[1].rsplit(".", 1)[0]
    ns = [o.self_ns for chip in r.trace.ops for o in chip
          if o.custom and TRANSFORMS.sub("", o.name.lstrip("%")).startswith(kernel)]
    if not ns:
        return None
    return 1e3 * sum(ns) * 1e-9 / max(len(r.trace.ops), 1) / n
