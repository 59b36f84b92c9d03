"""Scan kernel: per statement, the sum of its `device.wait` spans — the
host blocked on the kernel's result.  `scan_device_ms` times the same
work from the device's side."""
from benchmark import span_reduce


def read(ctx):
    return span_reduce.per_statement_ms(
        ctx, lambda t: span_reduce.total_ns(t, "device.wait"))
