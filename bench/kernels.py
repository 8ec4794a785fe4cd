"""Which device ops in a trace run the expert FFN's GEMMs.

A Pallas kernel runs as a ``tpu_custom_call`` whose HLO name comes from
the function that encloses it: the wgrad kernel is ``gmm_pallas_wgrad.N``,
while the forward and dgrad kernels sit in a ``lax.cond`` and are named
``branch_1_fun.N``.  The name alone therefore does not tell a GEMM from
another kernel; the op's ``op_name`` metadata does: every grouped GEMM
kernel lies under ``jit(gmm_pallas...)``, the quantizers under
``jit(quantize_tilewise_pallas)`` and ``jit(act_quantize_pallas)``.  So
the GEMM ops are read from the optimized HLO of the programs the window
ran, and matched by name against the trace's op line.
"""
from __future__ import annotations

import re

GEMM_SCOPE = "jit(gmm_pallas"
_INSTR = re.compile(r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=')
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_MODULE = re.compile(r'^\s*HloModule\s+([\w.\-]+)', re.M)


def expert_gemm_ops(hlo_texts) -> frozenset:
    """HLO names of the Pallas custom calls under ``jit(gmm_pallas...)``
    in the given optimized HLO module texts."""
    names = set()
    for text in hlo_texts:
        for line in text.splitlines():
            if 'custom_call_target="tpu_custom_call"' not in line:
                continue
            op = _OP_NAME.search(line)
            ins = _INSTR.match(line)
            if op and ins and GEMM_SCOPE in op.group(1):
                names.add(ins.group(1))
    return frozenset(names)


def expert_kernel_seconds(trace, gemm_ops) -> float:
    return trace.ops_matching(lambda name: name in gemm_ops)[1]


def module_name(hlo_text: str) -> str:
    """The name a compiled program's runs carry on the trace's module
    line (there followed by ``(<fingerprint>)``)."""
    return _MODULE.search(hlo_text).group(1)
