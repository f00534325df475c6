"""Model FLOPs of every token the window processed over the window times
the chip's peak (``flops.window_model_flops``): the whole served step's
share of the chip's peak."""

from bench import flops


def read(rec):
    f = flops.window_model_flops(rec.dims, rec.attend_steps, rec.admits)
    return 100.0 * f / (rec.window_s * rec.peaks["bf16_flops"])
