"""Model step: XLA compiles (persistent-cache loads included) inside
the window, from the engine's process-wide count (``stats``
``xla_compiles``). An engine that does not count them gives its own
count of new served programs (``prefill_compiles`` and
``decode_compiles``)."""


def read(win):
    if "xla_compiles" not in win.stats["open"]:
        return float(win.delta("stats", "prefill_compiles")
                     + win.delta("stats", "decode_compiles"))
    return float(win.delta("stats", "xla_compiles"))
