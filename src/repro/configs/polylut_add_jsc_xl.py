"""PolyLUT-Add JSC-XL-Add2 — the jet-substructure model of PolyLUT-Add
(arXiv:2406.04910, model table, row JSC-XL-Add2): PolyLUT's JSC-XL
layers (128, 64, 64, 64, 5 neurons, beta = 5) with every hidden neuron
the sum of A = 2 sub-neurons of F = 2 inputs each.

As the repository models an adder node (``LUTGraphConfig``): each branch
owns an L-LUT of its F source codes, the branch codes are summed into a
beta + 1 = 6-bit code, and the next node reads those sums, so the inner
branch tables have 2^(6*2) entries.  The input layer reads 7-bit codes
(PolyLUT's JSC-XL input precision): 2^14-entry branch tables.  The
classifier is one L-LUT per class (the graph ends in an arity-1 node).
"""
from repro.config import register
from repro.core.nl_config import INPUT, LUTGraphConfig, LUTNodeSpec


def full() -> LUTGraphConfig:
    return LUTGraphConfig(
        name="polylut-add-jsc-xl",
        in_features=16,
        num_classes=5,
        beta=5,
        beta_in=7,
        nodes=(
            LUTNodeSpec(name="add0", width=128, fan_in=2,
                        inputs=(INPUT,), arity=2),
            LUTNodeSpec(name="add1", width=64, fan_in=2,
                        inputs=("add0",), arity=2),
            LUTNodeSpec(name="add2", width=64, fan_in=2,
                        inputs=("add1",), arity=2),
            LUTNodeSpec(name="add3", width=64, fan_in=2,
                        inputs=("add2",), arity=2),
            LUTNodeSpec(name="cls", width=5, fan_in=2,
                        inputs=("add3",), arity=1),
        ),
        kind="subnet",
        depth=4,
        width=16,
        skip=2,
        degree=3,
    )


def reduced() -> LUTGraphConfig:
    return LUTGraphConfig(
        name="polylut-add-jsc-xl-reduced",
        in_features=16,
        num_classes=5,
        beta=3,
        beta_in=4,
        nodes=(
            LUTNodeSpec(name="add0", width=16, fan_in=2,
                        inputs=(INPUT,), arity=2),
            LUTNodeSpec(name="add1", width=8, fan_in=2,
                        inputs=("add0",), arity=2),
            LUTNodeSpec(name="cls", width=5, fan_in=2,
                        inputs=("add1",), arity=1),
        ),
        kind="subnet",
        depth=2,
        width=4,
        skip=2,
    )


register("polylut-add-jsc-xl", full, reduced)
