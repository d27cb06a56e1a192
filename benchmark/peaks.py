"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports
it. The one table the benchmark reads; nothing in the environment
overrides it, and a device that is not here is an error."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16 a chip,
    # 16 GB HBM2e at 819 GB/s. JAX names the chip "TPU v5 lite".
    "TPU v5 lite": {"flops_per_s": 197e12, "bytes_per_s": 819e9,
                    "source": "Google Cloud TPU v5e"},
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError("no published peaks for device_kind %r in "
                       "benchmark/peaks.py (it holds %s); add the chip "
                       "with its source" % (device_kind, sorted(PEAKS)))
