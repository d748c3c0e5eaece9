"""lm_block_step_ms: device time of the LM chapter trainer's block-step
programs (``lm_block_step*``) per block step of the job."""


def read(ctx):
    seconds = sum(t for name, (t, _) in ctx.reduced.by_program.items()
                  if name.startswith("lm_block_step"))
    if not seconds:
        return None
    return 1e3 * seconds / ctx.layer_steps
