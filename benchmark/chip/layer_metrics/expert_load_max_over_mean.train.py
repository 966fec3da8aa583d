"""Largest over mean tokens of a held expert in the window's last step, of
the routed layer where that ratio is worst: 1 is a level load, and the
dropless layer computes the largest group whatever it is. From the program's
counter (``telemetry.moe_report()``); None where the program keeps none."""


def read(obs):
    try:
        from mxnet_tpu import telemetry
        report = telemetry.moe_report()
    except (ImportError, AttributeError):
        return None
    if not report:
        return None
    ratios = [row["max"] / row["mean"] for row in report["layers"]
              if row["mean"] > 0]
    return max(ratios) if ratios else None
