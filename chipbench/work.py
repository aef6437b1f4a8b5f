"""The least work one consensus epoch needs, from the system's own sizes.

Any implementation of an APC epoch has to read every stored entry of A
once, whatever its tile shape, projector form or Gram solver. So the count
is taken from the system and never from a layout, and a later change to
the layout can neither make it stale nor push a roofline share past 100%:

* dense path: the m·n float32 entries of the (augmented) matrix, and one
  product with the k columns of the state (2·m·n·k operations);
* blocked-ELL path: each of the nnz entries as a 4-byte value and a 4-byte
  index, used by one forward and one transpose product (4·nnz·k).

On several chips each chip does its share, ``1/chips`` of the whole.
"""
from __future__ import annotations

import json
import pathlib

PEAKS = pathlib.Path(__file__).with_name("peaks.json")


def epoch_work(path: str, m: int, n: int, nnz: int, k: int) -> tuple[float, float]:
    """(operations, bytes) of one epoch of a k-column batch, whole system."""
    if path == "dense":
        return 2.0 * m * n * k, 4.0 * m * n
    if path in ("matfree", "matfree_sharded"):
        return 4.0 * nnz * k, 8.0 * nnz
    raise ValueError(f"no work count for path {path!r}")


def peaks(device_kind: str) -> dict:
    """The peak rates of one chip of this kind; an unknown kind is an error."""
    table = json.loads(PEAKS.read_text())
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {PEAKS.name}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]


def least_seconds(flops: float, nbytes: float, peak: dict) -> tuple[float, str]:
    """The roofline's least time for that work on one chip, and which bound
    binds (``"bytes"`` or ``"flops"``)."""
    t_flops = flops / peak["flops_per_s"]
    t_bytes = nbytes / peak["bytes_per_s"]
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
