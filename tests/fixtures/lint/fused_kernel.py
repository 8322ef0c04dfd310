# zoolint: hot-path
"""zoolint fixture: the kernel-sweep driver idiom (a loop over tiles,
as in the ops/ dispatch smoke loops).  Draining every tile's result with a
per-iteration ``.block_until_ready()`` serializes dispatch against the
device and fires JG-TRANSFER-HOT; the shipped drivers enqueue the whole
tile sweep asynchronously and sync ONCE on the last handle, which is
the twin that must stay quiet."""


def per_tile_block(tiles, kernel_fn):
    outs = []
    for t in tiles:
        out = kernel_fn(t)
        out.block_until_ready()        # JG-TRANSFER-HOT fires: one
        # dispatch-drain per tile
        outs.append(out)
    return outs


def batched_tiles_ok(tiles, kernel_fn):
    outs = [kernel_fn(t) for t in tiles]   # quiet: async enqueue
    if outs:
        outs[-1].block_until_ready()       # quiet: ONE sync, after
        # the loop
    return outs
