"""As ``pallas_time_share.train`` reads it: here the grouped products, flash
attention forward and backward and the norms together."""
from benchmarks import loader

read = loader.Manifest(loader.ROOT).reader("pallas_time_share.train")
