"""Controls: cells whose program is broken on purpose, which
``correct`` has to refuse.

A control is a cell like any other (``workloads/``, ``configs/``,
``traffic/`` under this directory; ``--cells-root benchmark/controls``):
its configuration is the benchmark's own, key for key, plus
``"control": <name>``, which the family's ``build`` hands to the
module of its name here. That module returns the family's loss with
one path of the program left out or lowered in precision; the init,
the reference and everything the harness does stay as they are. So
a control goes through the comparison the cell goes through:

    python3 benchmark/run.py --cells-root benchmark/controls \
        --workload granite-4.0-h-micro.no_carry --seed 11 --seconds 4 --trace 0
    python3 benchmark/calibrate_reference.py --cells-root benchmark/controls \
        --workload granite-4.0-h-micro.no_carry --seeds 11,12,13 --rows 2

The first has to print ``"correct": false`` with the reference check
as the reason; the second's ``bf16`` reading is the broken program
against the honest reference (``kinds/common.reference_error``).
``BENCHMARK.json`` lists none of them: they are run once by the PR
that sets or changes what they guard, and the readings go into
PERF.md. tests/benchmark/test_controls_cpu.py holds each control's
configuration to the benchmark's and rehearses the toy ones.
"""
