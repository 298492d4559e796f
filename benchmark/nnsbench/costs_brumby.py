"""Operations and bytes the ``brumby`` configurations' scoring pass
needs, from their shapes alone: the useful work of the published
mathematics, whatever implements it. A layer counts its projections
(q, k, v, the gate's and the output's) and its MLP at two operations a
parameter and token, and the power retention as the **token-by-token
form** states it: ``phi`` of a head's ``hd`` channels is its symmetric
square, ``hd (hd + 1) / 2`` rows (8256 at 128); a token reads the state
once for each query head and updates it once for each key/value head,
``2 x rows x hd`` operations each. A chunked form's products inside a
chunk, the padding of a layout that keeps more rows than ``phi`` has,
and the normaliser's small products are what an implementation chooses
and are not counted: a kernel that multiplies a padded 16384-row square
reads under 50 % of :func:`retention_floor_s` and none reads over 100.
A test holds each function to hand-worked counts."""
from __future__ import annotations

BYTES = 2       # bfloat16 operands
FLOAT = 4       # the log-gates and the state are float32


def phi_rows(cfg: dict) -> int:
    """Rows of the symmetric square of a head's channels."""
    hd = cfg["head_dim"]
    return hd * (hd + 1) // 2


def layer_params(cfg: dict) -> int:
    """A layer's matrices: q and output over all query heads, k and v
    over the key/value heads, the gate a key/value head, the MLP's three
    (the norms' vectors and the gate's offsets left out)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return (2 * d * h * hd + 2 * d * hkv * hd + d * hkv
            + 3 * d * cfg["intermediate_size"])


def retention_flops(cfg: dict, s: int) -> float:
    """The retention of one layer over ``s`` tokens, token by token:
    every query head reads its state, every key/value head updates
    its own."""
    return 2.0 * s * phi_rows(cfg) * cfg["head_dim"] * (
        cfg["num_attention_heads"] + cfg["num_key_value_heads"])


def retention_bytes(cfg: dict, s: int) -> float:
    """What any form of one layer's retention over a buffer of ``s``
    tokens has to move: q, k and v in and o out once at the stream's
    width, a float32 log-gate a key/value head and token in, and the
    carried state (``phi x hd`` and ``phi`` float32 a key/value head)
    read once and written once a buffer."""
    hd = cfg["head_dim"]
    h, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return float(s * (BYTES * hd * (2 * h + 2 * hkv) + FLOAT * hkv)
                 + 2 * FLOAT * hkv * phi_rows(cfg) * (hd + 1))


def retention_floor_s(cfg: dict, s: int, peaks: dict) -> float:
    """The least seconds the chip could take over one buffer's
    retentions: every layer's larger of operations over the peak rate
    and bytes over the memory's (the operations', at these sizes)."""
    return cfg["num_hidden_layers"] * max(
        retention_flops(cfg, s) / peaks["flops_bf16"],
        retention_bytes(cfg, s) / peaks["hbm_bytes_per_s"])


def buffer_flops(cfg: dict, s: int) -> float:
    """One buffer's scoring pass: every layer's matrices and retention,
    and the head at every position (the log-probabilities need each
    position's logits). The same whatever the buffer's place in its
    document: the retention's work does not grow with the context."""
    return cfg["num_hidden_layers"] * (
        2.0 * s * layer_params(cfg) + retention_flops(cfg, s)) \
        + 2.0 * s * cfg["hidden_size"] * cfg["vocab_size"]
