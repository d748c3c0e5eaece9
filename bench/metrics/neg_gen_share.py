"""neg_gen_share: the share of the traced window's device time (all
chips) spent scoring the train set for AdaptiveNEG: the executions of
the goodness-scoring program that are not the final evaluation's."""


def read(ctx):
    neg = sum(c.count for c in ctx.job_calls
              if c.what == "AdaptiveNEG scoring" and c.K == ctx.model[
                  "layer_sizes"][0])
    ev = sum(c.count for c in ctx.job_calls
             if c.what == "evaluation" and c.K == ctx.model["layer_sizes"][0])
    seconds, runs = ctx.reduced.by_program.get("goodness_class_scores",
                                               (0.0, 0))
    if not neg or runs != neg + ev:
        return None
    return 100.0 * seconds * neg / runs / (ctx.reduced.window_s * ctx.chips)
