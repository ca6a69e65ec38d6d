"""zbench: the served path of zeebe-tpu measured from the client's side.

``python3 -m zbench --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once, in a new process, and prints one
JSON result as the last line of standard output. ``zbench/README.md`` says
how the files fit together and how a later PR adds to them.
"""
