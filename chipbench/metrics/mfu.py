"""Train step: useful model FLOPs over the window, as a share of the chips'
bf16 peak. Useful FLOPs are three forwards per token with the causal
triangle only and no recomputation (``chipbench/arch/<model_type>.py``)."""


def read(run):
    if run.window_s <= 0 or not run.steps:
        return None
    done = run.useful_flops_per_token * run.tokens_per_step * run.steps
    return 100.0 * done / (run.window_s * run.chips * run.peak["bf16_flops"])
