"""Benchmark of the trainer on TPU chips: ``python bench/run.py``."""
