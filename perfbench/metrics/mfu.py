"""Model FLOP/s utilisation of the traced window, in %: the operations the
forward and backward passes need per token (the configuration's plain
reference counts them, without recomputation) times the tokens trained
per second in the window, over the chips' peak bf16 FLOP/s."""


def read(record):
    if record.peak is None or record.steps == 0:
        return None
    achieved = record.flops_per_token * record.tokens / record.window_s
    return 100.0 * achieved / (record.chips * record.peak.flops_bf16)
