"""Device self time under the scopes ``attn_qkv`` and ``attn_out`` (the
attention block's projections, with the norm, RoPE and residual add that
ride in them; all phases) as a share of the traced steps' device time."""

from benchmark import trace_scopes


def read(run):
    return trace_scopes.step_share_pct(run, ("attn_qkv", "attn_out"))
