"""The program's Pallas ``flash_attention`` (causal prefill attention).

One call per layer per prefill, over the whole batch. The operations
are the causal ones the algorithm needs (query i meets keys 0..i, for
QK^T and for PV); the bytes are q, k and v read once and the output
written once, in the served dtype. The kernel computes skipped tiles
above the diagonal as no work and re-reads k and v per query block;
neither is counted, so waste shows as a lower share.
"""
from __future__ import annotations

#: substrings of the kernel's op name in the device trace
NAMES = ("_flash_kernel", "flash_attention")


def cost(B: int, S: int, H: int, K: int, hd: int, el: int = 2):
    """(FLOPs, bytes) of one call: B rows of S positions, H query heads
    over K key/value heads of size hd, el bytes per element."""
    flops = 4.0 * B * H * hd * S * (S + 1) / 2
    nbytes = el * B * S * (2 * H * hd + 2 * K * hd)
    return flops, nbytes


def calls(run) -> list:
    """(FLOPs, bytes) of every call the run's executed prefills made."""
    d = run.dims
    one = [cost(b, run.prompt_tokens, d["H"], d["K"], d["hd"])
           for b in run.prefill_batches]
    return [c for c in one for _ in range(d["L"])]
