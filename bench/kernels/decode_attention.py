"""The program's Pallas ``decode_attention`` (one new token against the
cache).

One call per layer per decode step, over the whole batch. The operations
and bytes are what the algorithm needs for the keys that are live: the
new token at position p attends to p + 1 keys, so k and v of p + 1
positions are read (with their position ids), q read and the output
written. The kernel reads the whole cache, padding included; that is not
counted, so the padding shows as a lower share.
"""
from __future__ import annotations

#: substrings of the kernel's op name in the device trace
NAMES = ("_decode_kernel", "decode_attention")


def cost(B: int, n_keys: int, H: int, K: int, hd: int, el: int = 2):
    """(FLOPs, bytes) of one call: B rows, each attending to n_keys keys."""
    flops = 4.0 * B * H * hd * n_keys
    nbytes = el * B * (2 * n_keys * K * hd + 2 * H * hd) + 4 * B * n_keys
    return flops, nbytes


def calls(run) -> list:
    """(FLOPs, bytes) of every call the run's executed decode steps made."""
    d = run.dims
    one = [cost(b, pos + 1, d["H"], d["K"], d["hd"])
           for b, pos in run.decode_steps]
    return [c for c in one for _ in range(d["L"])]
