"""step_mfu (%): the whole training step's share of the chips' bf16 peak:
images per second of the traced window times the dense model's training
FLOPs per image (6 x forward MACs, pinned in the configuration file,
counted whatever SLU skipped) over chips x peak FLOP/s from
bench/peaks.json."""


def read(record, trace):
    peak = record.get("peak")
    if not peak or record.get("images_per_s", 0) <= 0:
        return None
    return (100.0 * record["images_per_s"] * record["flop_per_image"]
            / (record["chips"] * peak["bf16_flops_per_s"]))
