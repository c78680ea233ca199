"""The benchmark: yardstick code and data that later PRs may add to but
not edit.  ``python3 benchmarks/run.py --workload <cell> --seed <n>
--seconds <s> --trace <0|1>`` runs one cell once; see README.md."""
