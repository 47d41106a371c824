"""KERNEL_META for the label_join package — checked by the kernel-shape
sanitizer (``python -m repro.analysis``, DESIGN.md §15).

Pure literal by contract (``ast.literal_eval`` is the parser). The packed
variant reduces uint32 label words to dense int32 (hits, hub) outputs;
its padding story is ``"slice"`` — the ops.py wrapper zero-extends padded
queries in and slices ``[:q]`` back out, and zero padding bits contribute
neither hits nor hub candidates (popcount/ctz of 0). The packed kernel
writes its per-query answers as [Q, 1] columns (lane reductions).
"""

KERNEL_META = {
    "package": "label_join",
    "vmem_budget_bytes": {"tpu": 16777216},
    "dims": {},
    "kernels": {
        "label_join_pallas": {
            "tiles": {"tq": 256, "tl": 256},
            "align": {"tq": 8, "tl": 128},
            "divides": {"q": ["tq"], "l": ["tl"]},
            "operands": {
                "out_rows": {"block": ["tq", "tl"], "dtype": "int32"},
                "in_rows": {"block": ["tq", "tl"], "dtype": "int32"},
            },
            "outputs": {
                "hits": {"block": ["tq"], "dtype": "int32"},
                "hub": {"block": ["tq"], "dtype": "int32"},
            },
            "packed": False,
            "pad_safety": None,
            "wrapper": "label_join",
            "ref": "label_join_ref",
            "scratch_bytes": 0,
        },
        "label_join_packed_pallas": {
            "tiles": {"tq": 256, "tw": 128},
            "align": {"tq": 8, "tw": 128},
            "divides": {"q": ["tq"], "w": ["tw"]},
            "operands": {
                "out_words": {"block": ["tq", "tw"], "dtype": "uint32"},
                "in_words": {"block": ["tq", "tw"], "dtype": "uint32"},
            },
            "outputs": {
                "hits": {"block": ["tq", 1], "dtype": "int32"},
                "hub": {"block": ["tq", 1], "dtype": "int32"},
            },
            "packed": True,
            "pad_safety": "slice",
            "wrapper": "label_join_packed",
            "ref": "label_join_packed_ref",
            "scratch_bytes": 0,
        },
    },
}
