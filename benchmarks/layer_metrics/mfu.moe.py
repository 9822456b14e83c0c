"""As ``mfu.train`` reads it, of the expert model's train step: the family's
``counts.train_flops_per_token`` has the expert term at the EXPECTED held
choices (2 of a token's 4 where 16 of 32 are held); remat's second forward is
not counted."""
from benchmarks import loader

read = loader.Manifest(loader.ROOT).reader("mfu.train")
