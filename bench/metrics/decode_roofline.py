"""Share of the roofline reached by the decode steps: the least time the
window's decode steps could take (each step's least bytes over the chip's
bandwidth, or its FLOPs over the peak, whichever is larger; bench/harness
/widths.py counts both from the published widths and the live lengths)
over their measured device time."""


def read(run):
    t = run.trace
    if t is None or not t.decode_programs or not run.peaks:
        return None
    w, p = run.widths, run.peaks
    least = sum(max(w.decode_step_bytes(lv) / p["hbm_bytes_per_s"],
                    w.decode_step_flops(lv) / p["bf16_flops"])
                for lv in run.decode_lives)
    # the traced programs' mean time over every decode step of the window
    step_s = t.decode_device_s / t.decode_programs
    return least / (step_s * run.decode_calls) * 100.0
