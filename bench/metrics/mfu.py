"""mfu: model FLOPs of the traced job over its wall time, the chips and
the chip's bf16 peak. The program computes in f32 at HIGHEST (several
bf16 passes per product), so this share is low by construction."""


def read(ctx):
    return 100.0 * ctx.job_flops / (
        ctx.reduced.window_s * ctx.chips * ctx.peak.flops_per_s)
