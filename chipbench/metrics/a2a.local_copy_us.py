"""Device time per epoch of the ops that are neither collectives nor
Mosaic kernels (pack, unpack, copies), on the fullest chip (us)."""


def read(ctx):
    lay = ctx["layer"]
    if not lay.get("epochs"):
        return None
    ns = ctx["trace"].fullest().class_ns.get("other", 0)
    return ns * 1e-3 / lay["epochs"]
