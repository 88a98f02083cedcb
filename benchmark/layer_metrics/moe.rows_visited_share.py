"""The rows of the row tiles the grouped-product kernels VISIT (a tile
once for each group in it) over the rows of the static buffer, ``T * k``
rounded up to a tile: mean over the expert layers, as ``loss_fn``'s
metrics report it (``moe_rows_visited_share``, from the schedule's own
count of visits).  Read from the program's side of the reference check —
one forward pass of ``loss_fn`` at step 0 on the seeded sample of the
cell's own sequence length (``check_rows`` rows), whose metrics the loop
keeps whole as ``check.program_parts``; the window fetches only what the
reference module's ``STEP_METRICS`` names, a file this reader's PR could
not edit.  Where every expert is held it is 1 plus the tiles that two
groups share (about 1.1-1.25); of one chip's share of a layer divided
over 8 it follows ``moe.held_rows_share``, about 1 / 8 plus a tile a
group.  None where the program reports no such metric (a program from
before PR 39, a model without experts)."""


def read(run):
    return run["worker"].get("check", {}).get("program_parts", {}).get(
        "moe_rows_visited_share")
