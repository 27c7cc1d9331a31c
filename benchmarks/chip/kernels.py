"""How the trace names the program's Pallas kernels.

A TPU trace names each operation by its HLO text (``%closed_call.171 =
bf16[4,192,4096]{...} custom-call(...), custom_call_target=
"tpu_custom_call", operand_layout_constraints={...}``); the program
gives its kernels no names of their own.  So each kernel is told apart
by its custom call's signature: the operand list in
``operand_layout_constraints``, or the output, in the order the
kernel's ``pallas_call`` takes them.
"""
_OPS = r"operand_layout_constraints=\{"
_I1 = r"s32\[\d+\]\{0\}"                        # flat index / page table
_ND = r"bf16\[\d+,\d+,\d+(?:,\d+)+\]\{[\d,]+\}"  # [L, ...] view or arena

# kernels/sparse_attention.py: kv_len, q positions [B, kq, 1], q, k, v
SPARSE_ATTENTION = _OPS + _I1 + r", s32\[\d+,\d+,1\]\{2,1,0\}, bf16"
# kernels/proxy_score.py proxy_score_paged: -> (scores [B, N, 1], p_now)
PROXY_SCORE_PAGED = (r"= \(f32\[\d+,\d+,1\]\{[^}]*\}, bf16\[\d+,\d+,\d+\]"
                     r"\{[^}]*\}\) custom-call")
# kernels/scatter_update.py gather_pages: page table, arena -> view
GATHER_PAGES = _OPS + _I1 + ", " + _ND + r"\}"
# scatter_pages: page table, dense view, arena (aliased)
SCATTER_PAGES = _OPS + _I1 + ", " + _ND + ", " + _ND + r"\}"
# scatter_rows_paged: row index, page table, rows, one layer's arena
SCATTER_ROWS_PAGED = _OPS + _I1 + ", " + _I1 + ", "
