"""The benchmark's own yardstick: peaks, FLOP/byte arithmetic, percentiles,
trace reduction, manifest loading.  Imports nothing of that sort from
``deepspeed_tpu``: a later PR may not move the yardstick."""
