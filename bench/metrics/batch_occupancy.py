"""Decode slots in use per generate call over the whole window: the tokens
the program's ``engine.tokens`` counter gained during the decode ticks that
ended in the window, over what its ``stage.generate.calls`` gained in them."""


def read(ctx):
    w = ctx.window
    return w.decode_tokens / w.generate_calls if w.generate_calls else None
