"""Model FLOP/s utilization: the operations the forward and backward
passes need per token (from the configuration's shapes, by its reference
file; recomputation not counted) times the window's tokens per second,
over the chip's bf16 peak, in percent."""
import spec


def read(run):
    if run.peaks is None:
        return None
    per_token = spec.reference(run.model["reference"]).flops_per_token(
        run.model)
    steps = run.window[1] - run.window[0]
    rate = steps * run.tokens_per_step / run.window_s
    return 100.0 * per_token * rate / run.peaks["bf16_flops"]
