"""Operations and bytes of a network's conv work, and the chip's peaks.

A conv node's operations are 2 x its multiply-accumulates, groups
counted (a grouped conv does 1/groups of the dense work). Pools and
residual adds are left out. Its bytes are what it must move at the
least: the input, the output (after its fused pool) and any residual
operand once per image, and the weights and bias once per batch, at the
cell's precision. The least time of a node is the larger of operations
over the peak rate and bytes over the HBM bandwidth.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS = Path(__file__).resolve().parent / "peaks.json"

ELEM_BYTES = {"fp32": 4, "bf16": 2, "int8": 1}
# the chip publishes no float32 rate (HIGHEST runs several bfloat16
# passes), so float32 work is held against the bfloat16 peak
PEAK_KEY = {"fp32": "bf16_flops_per_s", "bf16": "bf16_flops_per_s",
            "int8": "int8_ops_per_s"}


def peaks(device_kind: str, path: Path = PEAKS) -> dict:
    """The peak table's entry for ``device_kind``; a kind missing from
    the table is an error, never a default."""
    table = json.loads(Path(path).read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in {path} "
                       f"(have {sorted(table)})")
    return table[device_kind]


def _out_hw(n: dict) -> "tuple[int, int]":
    oh = (n["in_h"] + 2 * n["pad"] - n["kernel"]) // n["stride"] + 1
    ow = (n["in_w"] + 2 * n["pad"] - n["kernel"]) // n["stride"] + 1
    return oh, ow


def _stored_hw(n: dict) -> "tuple[int, int]":
    oh, ow = _out_hw(n)
    if n["pool"] <= 1:
        return oh, ow
    pp = n.get("pool_pad", 0)
    return ((oh + 2 * pp - n["pool"]) // n["pool_stride"] + 1,
            (ow + 2 * pp - n["pool"]) // n["pool_stride"] + 1)


def conv_flops(n: dict) -> int:
    """Operations of one conv node for one image."""
    oh, ow = _out_hw(n)
    return (2 * oh * ow * n["out_c"] * n["kernel"] * n["kernel"]
            * n["in_c"] // n["groups"])


def conv_bytes(n: dict, batch: int, precision: str) -> int:
    """Least bytes one conv node moves for a batch of ``batch`` images."""
    e = ELEM_BYTES[precision]
    sh, sw = _stored_hw(n)
    per_image = n["in_h"] * n["in_w"] * n["in_c"] + sh * sw * n["out_c"]
    if n.get("residual"):
        oh, ow = _out_hw(n)
        per_image += oh * ow * n["out_c"]
    weights = (n["kernel"] * n["kernel"] * n["in_c"] // n["groups"]
               * n["out_c"] + n["out_c"])
    return e * (batch * per_image + weights)


def node_bounds(nodes, batch: int, precision: str, peak: dict) -> list:
    """Per node: (name, least seconds for one batch, "compute" | "memory")."""
    rate = peak[PEAK_KEY[precision]]
    bw = peak["hbm_bytes_per_s"]
    out = []
    for n in nodes:
        tc = batch * conv_flops(n) / rate
        tm = conv_bytes(n, batch, precision) / bw
        out.append((n["name"], max(tc, tm),
                    "compute" if tc >= tm else "memory"))
    return out


def least_seconds_per_batch(nodes, batch: int, precision: str,
                            peak: dict) -> float:
    return sum(t for _, t, _ in node_bounds(nodes, batch, precision, peak))


def flops_per_image(nodes) -> int:
    return sum(conv_flops(n) for n in nodes)


def peak_rate(peak: dict, precision: str) -> float:
    return peak[PEAK_KEY[precision]]
