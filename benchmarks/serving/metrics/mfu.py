"""Model step: useful model operations of the window's requests (each
prompt's prefill and each decode step at the context it really has,
``flops.py``) over the window's time and the chip's peak bf16 rate, in
percent."""

from .. import flops


def read(run):
    if run.peaks is None:
        return None
    ops = sum(flops.request_flops(run.family, run.model, r["prompt_len"],
                                  r["gen_len"]) for r in run.records)
    return 100.0 * ops / run.window_end_s / run.peaks["bf16_flops_per_s"]
