"""Compiles in the traced window of the train loop
(``runtime/trainer.py``): ``train.loop.compile`` spans (the loop's
compile ahead of a built step's first call) plus ``train.loop.recompile``
markers (a dispatch that compiled).  Nothing when the loop has no
``train.loop.dispatch`` span there."""


def read(r):
    inside = [s.name for s in r.trace.spans
              if r.window[0] <= s.start and s.end <= r.window[1]]
    if "train.loop.dispatch" not in inside:
        return None
    return float(sum(n in ("train.loop.compile", "train.loop.recompile")
                     for n in inside))
