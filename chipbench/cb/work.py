"""The work the served model needs, counted from its shapes: the FLOPs and
the HBM bytes that any implementation of a dense decoder has to spend.
It counts what the work requires, not what a given implementation does
(no padded rows, no padded page-table slots, no recomputation).

Per token through one layer (d model width, Hq query and Hkv key/value
heads of D, ff MLP width), as multiply-adds x 2:

    projections  2 d (Hq + 2 Hkv) D + 2 Hq D d
    MLP          3 * 2 d ff        (gate, up, down)
    attention    2 * 2 Hq D c      (scores and values over c keys)

and the LM head 2 d V for each position whose logits are used. Bytes of
a decode round: every weight the round needs once (bf16; the layers, the
final norm and the LM head, not the embedding table), plus the K and V of every key
a row attends (2 Hkv D bf16 per key per layer), plus the K and V the
round writes.
"""
from __future__ import annotations

from cb.weights import dims

BYTES = 2   # bf16


class Work:
    def __init__(self, spec: dict):
        m = dims(spec)
        self.m = m
        d, hq, hkv, hd, ff, L, V = (m[k] for k in
                                    ("d", "hq", "hkv", "hd", "ff", "L", "V"))
        self.layer_flops = (2 * d * (hq + 2 * hkv) * hd + 2 * hq * hd * d
                            + 3 * 2 * d * ff)
        self.attn_flops_per_key = 2 * 2 * hq * hd
        self.head_flops = 2 * d * V
        self.L = L
        self.kv_bytes_per_key = 2 * hkv * hd * BYTES * L
        layer_params = d * (hq + 2 * hkv) * hd + hq * hd * d + 3 * d * ff \
            + 2 * d + (2 * hd if m["qk_norm"] else 0)
        head_params = d * V + d        # the LM head and the final norm
        self.weight_bytes = (L * layer_params + head_params) * BYTES

    def token_flops(self, context: int, logits: bool) -> float:
        """One token at a position attending ``context`` keys."""
        return (self.L * (self.layer_flops + self.attn_flops_per_key * context)
                + (self.head_flops if logits else 0))

    def chunk_flops(self, offset: int, n: int) -> float:
        """A prefill chunk of n tokens at positions offset..offset+n-1
        (each attends itself and everything before it); the logits of
        its last position are computed."""
        keys = n * offset + n * (n + 1) // 2
        return (n * self.L * self.layer_flops
                + self.L * self.attn_flops_per_key * keys + self.head_flops)

    def decode_round(self, contexts) -> tuple:
        """(FLOPs, HBM bytes) of one decode round whose rows attend
        ``contexts`` keys each."""
        flops = sum(self.token_flops(c, True) for c in contexts)
        nbytes = (self.weight_bytes
                  + sum(contexts) * self.kv_bytes_per_key
                  + len(contexts) * self.kv_bytes_per_key)
        return flops, nbytes

    def least_time(self, flops: float, nbytes: float, peaks: dict) -> float:
        return max(flops / peaks["bf16_flops"],
                   nbytes / peaks["hbm_bytes_per_s"])
