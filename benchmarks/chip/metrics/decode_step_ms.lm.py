"""Device time of one decode step: the engine's jitted ``decode`` program
in the traced window, over its calls, in ms."""


def read(r):
    mod = r.trace["modules"].get("jit_decode")
    if not mod or not mod["calls"]:
        return None
    return 1e3 * mod["s"] / mod["calls"]
