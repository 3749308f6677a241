"""``benchmark/check.py`` for a configuration whose rows come from a
generator of its own and whose width needs the tiled reference
(``benchmark/reference_wide.py``): the same numbers beside the same kind of
limits (``check.py``'s docstring lists them, ``hessian_shortfall`` among
them), computed by ``check.py``'s own functions over the tiled walk.

Training traffic without a validation set only: no cell asks for more yet.
"""
from benchmark import check, reference_wide as reference


def check_cell(cell, seed, produced, n_train, n_valid, block_rows):
    if n_valid:
        raise ValueError("check_wide judges training without a validation "
                         f"set; {cell['name']} has {n_valid} validation rows")
    return check.check_cell(cell, seed, produced, n_train, 0, block_rows,
                            ref=reference)
