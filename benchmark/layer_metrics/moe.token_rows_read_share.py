"""The rows ONE token-side sum of an expert layer fetches (``_combine``'s
forward, ``_dispatch``'s gradient: ``ops/moe.py::_token_sum``) over the
``T * k`` (token, choice) pairs: mean over the expert layers, as
``loss_fn``'s metrics report it (``moe_token_rows_read_share``, from what
the code does: its trips over the live rows times the rows a trip
fetches, plus the ``T`` at the ends of the tokens' runs).  Read from the
program's side of the reference check, ``check.program_parts``, as
``moe.rows_visited_share`` is and for its reason: the window fetches only
what the reference module's ``STEP_METRICS`` names.  1.0 where every
expert is held (one gather of a row a (token, choice)); of one chip's
share it follows ``moe.held_rows_share`` by a chunk a call and a quarter
(``T`` rows of ``T * 4``).  None where the program reports no such metric
(a program from before PR 41, a model without experts)."""


def read(run):
    return run["worker"].get("check", {}).get("program_parts", {}).get(
        "moe_token_rows_read_share")
