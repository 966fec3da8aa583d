"""Published peaks of the devices the benchmark may run on.

Keyed by the exact ``device_kind`` jax reports. A device that is not here is
an error: there is no default and no environment override, so a CPU run can
never print a utilisation.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
    # at 819 GB/s, per chip
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


class UnknownDevice(Exception):
    """The device kind has no row in the peaks table."""


def peaks(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise UnknownDevice(
            f"device kind {device_kind!r} is not in the benchmark's peaks "
            f"table (known: {sorted(PEAKS)})") from None
