"""Read the program's own names in a profiler trace: its host spans, the
device scopes in each operation's ``op_name``, and its Pallas kernels.

The program (``src/repro/spans.py``) names them:

* host spans ``fl.round`` and its phases ``fl.round.simulate``, ``.data``,
  ``.dispatch``, ``.fetch``, ``.account``; ``fl.async.dispatch``,
  ``.train``, ``.commit``, ``.host_sync``;
* device scopes ``fl.local_train``, ``fl.commit``, ``fl.commit.pack``,
  ``fl.commit.unpack``, ``fl.server_step``;
* kernels ``fl_quantize``, ``fl_secure_commit``, ``fl_plain_commit``,
  ``fl_accum``, ``fl_topk``, ``fl_fedprox_update``, ``fl_selective_scan``:
  a Pallas call's instruction is named after its kernel, so the name is
  in the event's own name, ``%fl_secure_commit.1 = ... custom-call(...)``.

``reduce`` gives every key ``trace.reduce`` gives, with idle gaps named by
the innermost span of the program or of the benchmark, and two more:
``scopes``, device seconds by the innermost ``fl.`` scope of each
operation (``"(none)"`` for operations outside every scope), and
``kernels``, ``[seconds, events]`` by kernel name.  Loops and calls are
left out of both, as from the breakdown: the operations they hold are
counted.  ``kernel_seconds`` reads kernel time from ``trace.reduce``'s
``ops`` alone.

``run.py`` reduces its trace with ``trace.py`` and deletes it before the
metric readers run, so of this module the benchmark uses only
``kernel_seconds`` (the ``commit_kernel_ms`` reader); ``read_profile`` and
``reduce`` serve a trace kept by hand.
"""
from __future__ import annotations

import re
from collections import defaultdict

from chipbench import trace

NONE = "(none)"
SPAN_PREFIXES = ("fl.", trace.SPAN_PREFIX)
TPU_KERNEL = 'custom_call_target="tpu_custom_call"'
# the innermost scope: the last ``fl.`` component of the op_name path,
# also inside transform wrappers such as ``transpose(jvp(fl.local_train))``
_SCOPE = re.compile(r"(?:^|[/(])(fl\.[A-Za-z0-9_.]+)")
_SUFFIX = re.compile(r"\.\d+$")
_HLO_OP_NAME = re.compile(
    r'^\s*(?:ROOT )?%?([^\s=]+) = .*?metadata=\{[^}]*?op_name="([^"]*)"')


def scope_of(op_name: str | None) -> str:
    found = _SCOPE.findall(op_name or "")
    return found[-1] if found else NONE


def instruction(op: str) -> str:
    """``%fusion.12 = f32[8] fusion(...)`` -> ``fusion.12``."""
    return op.partition(" = ")[0].lstrip("%")


def kernel_of(op: str) -> str | None:
    """The Pallas kernel an operation runs (its instruction's name without
    the numeric suffix), or None for any other operation."""
    if TPU_KERNEL not in op:
        return None
    return _SUFFIX.sub("", instruction(op))


def kernel_seconds(ops: dict) -> dict:
    """{kernel: device seconds} from ``trace.reduce``'s ``ops``."""
    out = defaultdict(float)
    for op, s in ops.items():
        k = kernel_of(op)
        if k is not None:
            out[k] += s
    return dict(out)


def hlo_op_names(hlo_text: str) -> dict:
    """{instruction: op_name} from a compiled program's HLO text
    (``compiled.as_text()``), whose instruction names are the trace's."""
    out = {}
    for line in hlo_text.splitlines():
        m = _HLO_OP_NAME.match(line)
        if m:
            out[m.group(1)] = m.group(2)
    return out


def read_profile(pd):
    """``trace.read_profile``'s (device_ops, host_spans, modules), with the
    host spans of the program as well as of the benchmark."""
    device_ops, _, modules = trace.read_profile(pd)
    spans = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
             for plane in pd.planes if plane.name == trace.HOST_PLANE
             for line in plane.lines for ev in line.events
             if ev.name.startswith(SPAN_PREFIXES)]
    return device_ops, spans, modules


def reduce(device_ops: dict, host_spans: list, window: tuple,
           op_names: dict, top: int = 10, modules: dict | None = None):
    """``trace.reduce`` over the same arguments, plus ``scopes`` and
    ``kernels``; ``op_names`` maps instruction names to ``op_name``
    (``hlo_op_names``).  On a TPU the ``op_name`` also sits in each
    operation's event metadata, as its ``tf_op`` stat, which
    ``jax.profiler.ProfileData`` does not expose."""
    out = trace.reduce(device_ops, host_spans, window, top, modules)
    lo, hi = window
    scopes = defaultdict(float)
    kernels = defaultdict(lambda: [0.0, 0])
    for evs in device_ops.values():
        for op, s, e in evs:
            if trace.opcode(op) in trace.CONTAINERS:
                continue
            c = trace.clip([(s, e)], lo, hi)
            if not c:
                continue
            t = (c[0][1] - c[0][0]) * 1e-9 / len(device_ops)
            scopes[scope_of(op_names.get(instruction(op)))] += t
            k = kernel_of(op)
            if k is not None:
                kernels[k][0] += t
                kernels[k][1] += 1
    out["scopes"] = dict(scopes)
    out["kernels"] = {k: list(v) for k, v in kernels.items()}
    return out

