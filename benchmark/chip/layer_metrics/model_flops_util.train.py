"""6 x parameters x the traced run's own tokens/s over the device's bf16
peak: an end-to-end utilisation, not a roofline share. The cells compute in
float32 at default matmul precision; recomputation does not count."""
from chipbench import peaks


def read(obs):
    c = obs["counters"]
    peak = peaks.peaks(c["device_kind"])["bf16_flops_per_s"]
    return 100.0 * 6.0 * c["n_params"] * c["tokens_per_s"] / peak
