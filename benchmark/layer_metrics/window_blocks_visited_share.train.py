"""Share of the causal call's (Q block, K/V block) tiles that the window
kernels visit: the tiles of every traced ``window_attention_fwd`` /
``window_attention_bwd`` call over the tiles the causal call of the same
shapes and blocks visits, both counted by the program from shapes and block
sizes when it traces the call (``telemetry.flash_window_blocks()``, handed
over once after the window: ``after_window()``). In percent. Its floor is the
share of the causal PAIRS inside the window (75.0 at 8192 under 4096); the
edge tiles, visited whole for the part of them inside the band, lie on top.
100 says the window bounds no loop: a window call on an XLA branch counts
the chunks it walks on both sides. Nothing where the program published no
such counts."""
NAME = "window_blocks_visited_share.train"
UNIT = "%"
LAYER = "kernels"
MOVES = "train_samples_per_s"
SOURCE = "program_counter"


def read(run):
    kernels = (run.get("program") or {}).get("window_blocks") or {}
    causal = sum(k.get("causal", 0) for k in kernels.values())
    if not causal:
        return None
    return 100.0 * sum(k.get("visited", 0) for k in kernels.values()) / causal
