"""The filter's required model operations for the answered frames over
the time the chunks were in the system after their last frame arrived
(the sum over chunks of answer time minus the arrival of the chunk's
last frame), as a share of the chips' bf16 peak."""


def read(run):
    busy = sum(c.t_answer - c.last_arrival for c in run.rec.chunks)
    if not run.cell.live or busy <= 0:
        return None
    work = run.flops_per_frame * run.frames_answered
    return 100.0 * work / busy / (run.peak["bf16_flops_per_s"]
                                  * run.cell.chips)
