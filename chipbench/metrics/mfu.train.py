"""The whole training step's share of the chips' peak: model FLOPs of the
steps completed in the traced window (``chipbench/flops.py``, from the
configuration's shapes) over the window's seconds times the published bf16
peak (``chipbench/device.py``) of the chips in the trace."""


def read(record):
    reduced, peaks = record.get("reduced"), record.get("peaks")
    if reduced is None or not peaks or not record.get("steps") or not record.get("flops_per_step"):
        return None
    chips = len(record["trace"]["devices"])
    peak = peaks["bf16_flops"] * chips
    return 100.0 * record["flops_per_step"] * record["steps"] / (reduced["window_s"] * peak)
