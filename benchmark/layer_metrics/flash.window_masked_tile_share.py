"""Of the compute sub-tiles the windowed attention layers' kernels EXECUTE,
the share that lies on an edge — the diagonal or the window's far edge — and
so runs under a mask, as ``loss_fn``'s metrics report it
(``attn_window_masked_tile_share``: the flash schedule's count of live
sub-tiles by kind, from shapes, the largest over the windowed layers).  Read
from the program's side of the reference check, ``check.program_parts``, as
``moe.rows_visited_share`` is and for its reason: the window fetches only
what the reference module's ``STEP_METRICS`` names, and the number is the
same at every step of one shape.  0.12 at a window of 4096 under 8192 keys,
0.40 at 1024 under 16384: a window no wider than half a fetch tile puts
both edges in most tiles it touches.  None where the program reports no such
metric (a program from before PR 55, a model without a windowed layer)."""


def read(run):
    return run["worker"].get("check", {}).get("program_parts", {}).get(
        "attn_window_masked_tile_share")
