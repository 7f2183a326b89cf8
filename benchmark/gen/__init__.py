"""Config generators, one file per generator, found by the `generator`
name in a configuration file (`benchmark/configs/<config>.json`).

Each module exports `make_yaml(config, traffic, seed, scheduler,
experimental) -> str`.  They are copies of the generators in
`shadow_tpu/tools/netgen.py` (the yardstick must not move when the
program does); the program only ever receives the YAML they write.
"""
