"""lm_head_share: the share of the traced window's device time (all
chips) spent in the LM chapter trainer's head-step programs
(``lm_head_step*``)."""


def read(ctx):
    seconds = sum(t for name, (t, _) in ctx.reduced.by_program.items()
                  if name.startswith("lm_head_step"))
    if not seconds:
        return None
    return 100.0 * seconds / (ctx.reduced.window_s * ctx.chips)
