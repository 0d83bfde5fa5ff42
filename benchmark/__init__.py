"""The benchmark of paddle_tpu: see README.md beside this file."""
