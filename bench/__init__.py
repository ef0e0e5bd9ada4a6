"""Chip benchmark of the collective library and the model paths that
use it: ``python3 bench/run.py --workload <cell> --seed <n> --seconds
<s> --trace <0|1>``.  See ``harness.py`` for how a cell is found."""
