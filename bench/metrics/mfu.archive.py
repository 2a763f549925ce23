"""The filter's required model operations for the frames answered in the
window, over the window, as a share of the chips' bf16 peak."""


def read(run):
    if run.cell.live or run.window_s <= 0:
        return None
    work = run.flops_per_frame * run.frames_answered
    return 100.0 * work / run.window_s / (run.peak["bf16_flops_per_s"]
                                          * run.cell.chips)
