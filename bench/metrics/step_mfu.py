"""Model FLOPs of the work served in the window over the window times the
chip's bf16 peak.  Counted from the published widths (bench/harness/
widths.py): each real prompt token admitted in the window and each
decode step's tokens, 2 × the parameters used per token plus attention at
the live length; padding never counts."""


def read(run):
    if not run.peaks or run.window_s <= 0:
        return None
    w = run.widths
    flops = sum(w.decode_step_flops(lv) for lv in run.decode_lives)
    flops += sum(w.prompt_flops(n) for n in run.admit_prompts)
    return flops / (run.window_s * run.peaks["bf16_flops"]) * 100.0
