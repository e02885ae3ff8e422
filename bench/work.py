"""Required work, from shapes: the bytes a QAFeL upload and flush must move
through HBM when every value is read once and written once, and the bytes
of the two Pallas kernels whose rooflines the benchmark reports.

The byte counts follow the read-once/write-once rule: a kernel reads each
operand once and writes each result once, so time spent on padding, on
re-reads or on temporaries is not required work. A share computed from
them can only be an underestimate of what the chip moved, never above it.
"""
from __future__ import annotations

LANES = 128
F32 = 4


def rows_for(n: int) -> int:
    return -(-n // LANES)


def message_bytes(n: int, bits: int) -> int:
    """One packed qsgd message: n codes of ``bits`` bits plus one f32 norm
    per 128-value row."""
    return n * bits // 8 + F32 * rows_for(n)


def quantize_pack_bytes(n: int, bits: int) -> int:
    """Encode one n-value message: read n f32, write the packed message.
    The dither is computed in the kernel and reads nothing."""
    return F32 * n + message_bytes(n, bits)


def buffer_aggregate_bytes(n: int, bits: int, k: int) -> int:
    """Weighted dequantize-sum of k packed messages: read the k messages,
    write one f32 vector of n."""
    return k * message_bytes(n, bits) + F32 * n


def client_bytes(n: int, bits: int) -> int:
    """One client's upload, beyond its data: read x-hat (n f32), write the
    packed message. The local steps' intermediates stay on chip in the
    minimum."""
    return F32 * n + message_bytes(n, bits)


def flush_bytes(n: int, bits: int, sbits: int, k: int) -> int:
    """One flush: read the k uploads, read and write x, x-hat and momentum,
    write the broadcast."""
    return (k * message_bytes(n, bits) + 2 * 3 * F32 * n
            + message_bytes(n, sbits))


def step_work(n: int, bits: int, sbits: int, k: int, data_bytes: int,
              flops: float) -> dict:
    """Required FLOPs and bytes per absorbed upload: the client's step and
    its share of one flush."""
    return {"flops": float(flops),
            "bytes": float(client_bytes(n, bits) + data_bytes
                           + flush_bytes(n, bits, sbits, k) / k)}
