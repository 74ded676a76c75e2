"""Peak-flops tables and MFU arithmetic.

One home for the per-chip peak numbers the per-step telemetry records
read: public cloud.google.com/tpu specs, bf16 peak TFLOPS per chip
(v2/v3 per-chip = 2 cores), keyed by the EXACT ``device_kind`` jax reports. A kind that is
not in the table is an error, not a default. The ``cpu`` row is a
nominal 0.1 TFLOPS that exists only because tier-1 StepRecords are
priced against it (tests/unit/test_telemetry.py); it is never a device
metric and leaves with the benchmark PR (ROADMAP Design 1).
"""

PEAK_TFLOPS = {
    "TPU v2": 45.0, "TPU v3": 123.0, "TPU v4": 275.0,
    "TPU v5 lite": 197.0, "TPU v5e": 197.0, "TPU v5": 459.0,
    "TPU v5p": 459.0, "TPU v6 lite": 918.0, "TPU v6e": 918.0,
    "cpu": 0.1,
}


def lookup_device_kind(table, device, what):
    """``table[device_kind]`` for a jax Device or a device-kind string,
    exact match; an unknown kind raises — shared by the peak-flops and
    ICI-bandwidth tables."""
    kind = device if isinstance(device, str) else device.device_kind
    if kind not in table:
        raise KeyError(
            "no {} for device kind {!r}: known kinds are {}".format(
                what, kind, sorted(table)))
    return table[kind]


def peak_flops_for(device):
    """Peak flops/s for one chip of ``device`` (a jax Device or a
    device-kind string)."""
    return lookup_device_kind(PEAK_TFLOPS, device, "peak flops") * 1e12


def mfu_of(flops_per_step, step_time_s, n_devices, peak_flops_per_chip):
    """Achieved model-flops utilization: executed flops rate per chip
    over the chip's peak. Returns 0.0 on degenerate inputs."""
    if not flops_per_step or not step_time_s or step_time_s <= 0 or \
            not peak_flops_per_chip:
        return 0.0
    per_chip = flops_per_step / step_time_s / max(int(n_devices), 1)
    return per_chip / peak_flops_per_chip
