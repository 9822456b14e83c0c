"""As ``input_wait_ms.train`` reads it."""
from benchmarks import loader

read = loader.Manifest(loader.ROOT).reader("input_wait_ms.train")
