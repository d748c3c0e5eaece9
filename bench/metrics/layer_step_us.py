"""layer_step_us: device time of the chapter trainer programs
(``train_layer_chapter*``) per optimizer step of one layer."""


def read(ctx):
    seconds = sum(t for name, (t, _) in ctx.reduced.by_program.items()
                  if name.startswith("train_layer_chapter"))
    if not seconds:
        return None
    return 1e6 * seconds / ctx.layer_steps
