"""KERNEL_META for the bfs_pull_step package — checked by the
kernel-shape sanitizer (``python -m repro.analysis``, DESIGN.md §15).

Pure literal by contract (``ast.literal_eval`` is the parser): 16777216 =
16 MiB VMEM budget. ``q`` is the padded query-slab height and ``w`` the
packed frontier word count (V = 32768 -> 1024 words) assumed for the
static footprint estimate; operands are the pallas_call's own (the
wrapper folds alive and visited into one transposed [R, Q] "still to
visit" slab). ``scratch_bytes`` = three [TR, W] 32-bit temporaries of the
per-query scan (3 MiB). The frontier operand is packed but the OUTPUTS are
dense int32 rows, so there are no padding bits to protect on the way out
(packed: False).
"""

KERNEL_META = {
    "package": "bfs_pull_step",
    "vmem_budget_bytes": {"tpu": 16777216},
    "dims": {"q": 64, "w": 1024},
    "kernels": {
        "bfs_pull_step_pallas": {
            "tiles": {"tr": 256},
            "align": {"tr": 8},
            "divides": {"r": ["tr"]},
            "operands": {
                "frontier_words": {"block": ["q", "w"], "dtype": "uint32"},
                "adj_in_rows": {"block": ["tr", "w"], "dtype": "uint32"},
                "todo_t": {"block": ["tr", "q"], "dtype": "int32"},
            },
            "outputs": {
                "new_t": {"block": ["tr", "q"], "dtype": "int32"},
                "parent_t": {"block": ["tr", "q"], "dtype": "int32"},
            },
            "packed": False,
            "pad_safety": None,
            "wrapper": "multi_bfs_pull_step_rows",
            "ref": "bfs_pull_step_ref",
            "scratch_bytes": 3145728,
        },
    },
}
