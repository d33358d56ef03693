"""perfbench: the on-chip benchmark of deepspeed_tpu (see README.md)."""
