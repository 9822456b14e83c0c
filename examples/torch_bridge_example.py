"""Run an EXISTING torch pl.LightningModule distributed on TPU — the
reference's headline promise ("your module, now distributed",
ray_lightning/README.md:60-72), delivered by compilation instead of
wrapping: the bridge fx-traces the torch forward to JAX, translates
configure_optimizers() to optax, and ships the trained weights back into
the torch module.

Usage:
  python examples/torch_bridge_example.py --smoke-test           # local
  python examples/torch_bridge_example.py --num-workers 2        # actors
"""
from __future__ import annotations

import argparse


def main(num_workers: int = 0, max_epochs: int = 3, smoke_test: bool = False):
    import numpy as np
    import torch
    from torch import nn

    import ray_lightning_tpu as rlt

    # ---- the user's EXISTING torch module, written pl-style ------------
    class TorchMLP(nn.Module):
        def __init__(self, lr: float = 1e-2):
            super().__init__()
            self.lr = lr
            self.net = nn.Sequential(
                nn.Linear(32, 64), nn.ReLU(), nn.Dropout(0.1),
                nn.Linear(64, 10),
            )
            self.criterion = nn.CrossEntropyLoss()

        def forward(self, x):
            return self.net(x)

        def log(self, *args, **kwargs):  # pl provides this normally
            pass

        def training_step(self, batch, batch_idx):
            # a CUSTOM step — functional loss plus an activation-norm
            # auxiliary term. The bridge TRACES this body (self.log
            # inlines away), so these exact semantics run under jit;
            # an untraceable body refuses at adapt time.
            import torch.nn.functional as F

            x, y = batch
            logits = self(x)
            loss = F.cross_entropy(logits, y) + 1e-3 * (logits ** 2).mean()
            self.log("train_loss", loss)
            return loss

        def configure_optimizers(self):
            return torch.optim.Adam(self.parameters(), lr=self.lr)

    torch_module = TorchMLP()

    # ---- one call: it is now a native module -----------------------------
    adapted = rlt.interop.adapt_torch_module(torch_module)

    # synthetic linearly-separable data as (x, y) batches
    rng = np.random.default_rng(0)
    w = rng.normal(size=(32, 10))
    n = 256 if smoke_test else 2048
    xs = rng.normal(size=(n, 32)).astype(np.float32)
    ys = np.argmax(xs @ w, -1).astype(np.int32)
    batches = [(xs[i:i + 32], ys[i:i + 32]) for i in range(0, n, 32)]

    strategy = (
        rlt.RayStrategy(num_workers=num_workers, platform="cpu",
                        devices_per_worker=2)
        if num_workers else None
    )
    trainer = rlt.Trainer(
        max_epochs=max_epochs, strategy=strategy, logger=False,
        enable_checkpointing=False, enable_progress_bar=False, seed=0,
    )
    trainer.fit(adapted, train_dataloaders=batches, val_dataloaders=batches[:2])
    print("val metrics:", {k: float(v) for k, v in trainer.callback_metrics.items()})

    # ---- weights flow back into torch ------------------------------------
    trained = adapted.export_to_torch()
    trained.eval()
    with torch.no_grad():
        acc = float(
            (trained(torch.from_numpy(xs)).argmax(-1).numpy() == ys).mean()
        )
    print(f"torch-side accuracy after TPU-path training: {acc:.3f}")


if __name__ == "__main__":
    parser = argparse.ArgumentParser()
    parser.add_argument("--num-workers", type=int, default=0,
                        help="0 = in-process; N = RayStrategy worker actors")
    parser.add_argument("--max-epochs", type=int, default=3)
    parser.add_argument("--smoke-test", action="store_true")
    args = parser.parse_args()
    main(args.num_workers, args.max_epochs, args.smoke_test)
