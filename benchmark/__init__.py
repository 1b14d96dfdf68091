"""The benchmark of deepspeed_tpu: see README.md beside this file."""
