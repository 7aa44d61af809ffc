"""Work counted from a configuration's sizes: model FLOPs and the codec's
logical bytes.

These are the benchmark's own copies of the arithmetic. They read the
configuration file's ``as_run`` block and the traffic's token counts,
never the program, so a change to the program cannot change the yardstick.

FLOPs are the model's: two per multiply-add of every weight matrix a token
passes through, plus causal attention (the scores and the weighted sum over
the positions each query may see). Nothing recomputed counts twice.

Codec bytes are the logical bytes of one wire-codec call: the float32
message values in and the wire bytes out for an encode, the reverse for a
decode. The message is the activation of one tensor-parallel all-reduce,
taken from its unpadded shape, and the wire is the policy's layout.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Sequence

# bits -> the bit planes the codes are split into (bit splitting)
BIT_UNITS = {1: (1,), 2: (2,), 3: (2, 1), 4: (4,), 5: (4, 1), 6: (4, 2),
             7: (4, 2, 1), 8: (8,)}
META_BYTES = 2          # bfloat16 scale and zero per group


@dataclasses.dataclass(frozen=True)
class Dims:
    d_model: int
    n_heads: int
    n_kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    n_layers: int

    @classmethod
    def of(cls, as_run: Dict) -> "Dims":
        return cls(**{f.name: int(as_run[f.name])
                      for f in dataclasses.fields(cls)})

    @property
    def layer_matmul_params(self) -> int:
        """Weights of one block's matrices: q, k, v, o and the SwiGLU
        gate, up and down projections."""
        d, hd = self.d_model, self.head_dim
        attn = d * self.n_heads * hd * 2 + d * self.n_kv_heads * hd * 2
        return attn + 3 * d * self.d_ff


def attention_flops(dm: Dims, n_pairs: int) -> int:
    """Scores and weighted sum over ``n_pairs`` (query, key) pairs in
    every layer."""
    return dm.n_layers * 2 * 2 * dm.n_heads * dm.head_dim * n_pairs


def prefill_flops(dm: Dims, length: int) -> int:
    """One prompt of ``length`` tokens through every layer, causal, and
    the logits of its last position (the only ones prefill computes)."""
    linear = 2 * length * dm.n_layers * dm.layer_matmul_params
    pairs = length * (length + 1) // 2
    head = 2 * dm.vocab * dm.d_model
    return linear + attention_flops(dm, pairs) + head


def decode_step_flops(dm: Dims, contexts: Sequence[int]) -> int:
    """One decode step of a batch: one token per sequence, attending to
    ``contexts[i]`` positions (its own included)."""
    per_token = (2 * dm.n_layers * dm.layer_matmul_params
                 + 2 * dm.vocab * dm.d_model)
    return (len(contexts) * per_token
            + attention_flops(dm, int(sum(contexts))))


# ---------------------------------------------------------------------------
# wire codec
# ---------------------------------------------------------------------------

def wire_bytes(n: int, bits: int, group: int, spike: bool = False) -> int:
    """Bytes of the wire for one row of ``n`` values: the bit planes,
    then a scale and a zero per group, then two spikes (value and index)
    per group when spike reserving is on."""
    assert n % group == 0, (n, group)
    g = n // group
    planes = sum((n * u + 7) // 8 for u in BIT_UNITS[bits])
    meta = 2 * g * META_BYTES
    spikes = 2 * g * META_BYTES * 2 if spike else 0
    return planes + meta + spikes


def pad_to(x: int, m: int) -> int:
    return -(-x // m) * m


def allreduce_codec_calls(n_values: int, tp: int, bits: int, group: int,
                          spike: bool = False) -> Dict[str, list]:
    """The codec calls of one two-step all-reduce of ``n_values`` values
    over ``tp`` ranks, per rank: {"encode": [bytes, ...], "decode": [...]}.

    Phase 1 encodes the ``tp`` chunks of the message, exchanges them and
    decodes what arrives; phase 2 encodes this rank's summed chunk,
    gathers every rank's and decodes them.
    """
    n = pad_to(n_values, tp * group)
    chunk = n // tp
    w = wire_bytes(chunk, bits, group, spike)
    f32 = 4
    return {
        "encode": [n * f32 + tp * w, chunk * f32 + w],
        "decode": [tp * w + n * f32, tp * w + n * f32],
    }


def forward_codec(dm: Dims, tokens: int, tp: int, bits: int, group: int,
                  spike: bool = False) -> Dict[str, int]:
    """Codec calls and logical bytes of one forward over ``tokens``
    activations rows: the embedding's all-reduce and two per layer
    (attention out-projection, MLP down-projection)."""
    one = allreduce_codec_calls(tokens * dm.d_model, tp, bits, group,
                                spike)
    sites = 1 + 2 * dm.n_layers
    return {
        "encode_calls": sites * len(one["encode"]),
        "decode_calls": sites * len(one["decode"]),
        "bytes": sites * (sum(one["encode"]) + sum(one["decode"])),
    }


def hlo_codec_sites(period: int = 1) -> Dict[str, int]:
    """Codec kernels in a compiled forward's HLO: the embedding's
    all-reduce outside the layer scan, and the two all-reduces of each of
    the ``period`` blocks in the scanned body, two of each kind apiece."""
    sites = 1 + 2 * period
    return {"wire_encode": 2 * sites, "wire_decode": 2 * sites}
