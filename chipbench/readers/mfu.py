"""Model FLOP/s utilisation, in %: the operations forward and backward need
for one item (chipbench/flops.py, from shapes) x items per second, over the
chips' peak (chipbench/peaks.json). Recomputed operations do not count."""

from chipbench import flops


def read(records):
    if "items_per_s" not in records:
        return None
    peak = flops.peak(records["device_kind"])["bf16_flops_per_s"]
    return 100.0 * records["flops_per_item"] * records["items_per_s"] \
        / (peak * records["chips"])
