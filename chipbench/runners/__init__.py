"""Runners: one per way the program trains, named by a configuration's
`runner`. Each has a class `Run(cfg, mix, seed, devices)` with `setup()`,
`window(seconds)`, `free()` and `check()`."""
