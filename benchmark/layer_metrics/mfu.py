"""Model FLOP/s utilisation, end to end: items a second over the whole
window, times the operations the forward and backward passes need for one
item (from shapes; nothing recomputed counts), over chips times the
published bf16 peak."""

from benchmark.lib import shapes


def read(record):
    config, c = record.config, record.counters
    if "plan" in config:
        per_item = shapes.vgg_train_flops_per_image(
            config["plan"], config["image_size"], config["num_classes"],
            config["in_channels"])
    else:
        per_item = shapes.lm_train_flops_per_token(config, c["seq_len"])
    peak_flops, _ = shapes.peak(record.device["kind"])
    return (100.0 * c["items_per_s"] * per_item
            / (record.device["count"] * peak_flops))
