"""Operations and bytes of the flash-attention kernels
(``ops/attention.py``), one layer's call, from shapes alone."""
from typing import Any, Dict

from perf.costs.model import attention_matmul_flops
from perf.costs.model import attn_out
from perf.costs.model import kv_out


def flash_fwd_call(cfg: Dict[str, Any], batch: int, seq: int
                   ) -> Dict[str, float]:
    """One forward flash-attention call (one layer): QK^T and PV."""
    return {'flops': 2 * attention_matmul_flops(cfg, batch, seq),
            'bytes': _qkvo_bytes(cfg, batch, seq, tensors=1)}


def flash_bwd_call(cfg: Dict[str, Any], batch: int, seq: int
                   ) -> Dict[str, float]:
    """One layer's backward: the score map recomputed once, then dV,
    dP, dQ and dK - five matmuls, however many kernels and
    recomputations an implementation splits them into. Reads q, k,
    v, out and dOut; writes dq, dk, dv."""
    return {'flops': 5 * attention_matmul_flops(cfg, batch, seq),
            'bytes': _qkvo_bytes(cfg, batch, seq, tensors=2)}


def _qkvo_bytes(cfg: Dict[str, Any], batch: int, seq: int,
                tensors: int) -> float:
    """bf16 bytes of q, k, v and out, ``tensors`` times (forward: each
    once; backward: values and gradients)."""
    per_pos = 2 * attn_out(cfg) + 2 * kv_out(cfg)
    return 2.0 * tensors * batch * seq * per_pos
