"""KERNEL_META for the bfs_multi_step package — checked by the
kernel-shape sanitizer (``python -m repro.analysis``, DESIGN.md §15).

Pure literal by contract (``ast.literal_eval`` is the parser): 16777216 =
16 MiB VMEM budget. ``q`` is the full query-slab height (the engine's
admission cap pads to 64) and ``q32`` = q * 32, the bit-major parent rows
of the packed kernel. Operands are the pallas_call's own (the wrappers
pass the slab both as [Q, R] and transposed as [R, Q], and the packed
kernel alive/visited as words). ``scratch_bytes`` covers the per-query
[TR, TC] candidate slice plus the widened adjacency tile (dense: 2 x
256 KiB) or the masked word tile and per-bit temporaries (packed: 5 x
128 KiB).
"""

KERNEL_META = {
    "package": "bfs_multi_step",
    "vmem_budget_bytes": {"tpu": 16777216},
    "dims": {"q": 64, "q32": 2048},
    "kernels": {
        "multi_bfs_step_pallas": {
            "tiles": {"tr": 256, "tc": 256},
            "align": {"tr": 128, "tc": 128},
            "divides": {"rows": ["tr"], "v": ["tc"]},
            "operands": {
                "frontiers": {"block": ["q", "tr"], "dtype": "float32"},
                "frontiers_t": {"block": ["tr", "q"], "dtype": "float32"},
                "adj": {"block": ["tr", "tc"], "dtype": "uint8"},
                "alive": {"block": [1, "tc"], "dtype": "int32"},
                "visited": {"block": ["q", "tc"], "dtype": "int32"},
            },
            "outputs": {
                "new": {"block": ["q", "tc"], "dtype": "int32"},
                "parent": {"block": ["q", "tc"], "dtype": "int32"},
            },
            "packed": False,
            "pad_safety": None,
            "wrapper": "multi_bfs_step",
            "ref": "multi_bfs_step_ref",
            "scratch_bytes": 524288,
        },
        "multi_bfs_step_packed_pallas": {
            "tiles": {"tr": 256, "tw": 128},
            "align": {"tr": 8, "tw": 128},
            "divides": {"rows": ["tr"], "w": ["tw"]},
            "operands": {
                "frontiers_t": {"block": ["tr", "q"], "dtype": "float32"},
                "adj_packed": {"block": ["tr", "tw"], "dtype": "uint32"},
                "alive_words": {"block": [1, "tw"], "dtype": "uint32"},
                "visited_words": {"block": ["q", "tw"], "dtype": "uint32"},
            },
            "outputs": {
                "new_words": {"block": ["q", "tw"], "dtype": "uint32"},
                "parent": {"block": ["q32", "tw"], "dtype": "int32"},
                "reach_words": {"block": ["q", "tw"], "dtype": "uint32"},
            },
            "packed": True,
            "pad_safety": "slice",
            "wrapper": "multi_bfs_step_packed",
            "ref": "multi_bfs_step_packed_ref",
            "scratch_bytes": 655360,
        },
    },
}
