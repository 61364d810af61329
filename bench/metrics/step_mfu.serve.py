"""Model FLOPs of the window's prefills and decoded tokens over the
window's wall time, as a share of the chips' bf16 peak."""


def read(run):
    c = run.counts
    if not c["model_flops"]:
        return None
    return 100.0 * c["model_flops"] / (run.window_s * c["chips"]
                                       * run.peak["bf16_flops"])
