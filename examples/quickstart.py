"""Quickstart: Seeker's coreset pipeline in 60 seconds.

    PYTHONPATH=src python examples/quickstart.py

Builds both coreset kinds from a sensor window, shows the wire payloads
(the paper's 240 B -> 42 B arithmetic), recovers the window on the "host",
and runs the energy-aware decision flow over a harvested-energy trace.
"""
import jax
import jax.numpy as jnp

from repro.core import (TABLE2_COSTS, choose_decision, cluster_payload_bytes,
                        harvest_trace, importance_coreset, memo_decision,
                        predictor_forecast, predictor_init, predictor_update,
                        raw_payload_bytes, sampling_payload_bytes,
                        supercap_step)
from repro.core.coreset import channel_cluster_coresets
from repro.core.recovery import recover_cluster_window
from repro.data.sensors import class_signatures, har_window
from repro.kernels import kmeans_coreset_op, signature_corr_op


def main():
    key = jax.random.PRNGKey(0)

    # --- a sensing window (60 samples @ 50 Hz x 3 IMU channels) ------------
    window = har_window(key, jnp.asarray(4))
    print(f"window: {window.shape}, raw payload/channel = "
          f"{raw_payload_bytes(window.shape[0])} B")

    # --- clustering coreset (paper D3): 12 clusters/channel ----------------
    cs = channel_cluster_coresets(window, k=12, iters=4)
    print(f"cluster coreset: centers {cs.centers.shape}, "
          f"payload/channel = {cluster_payload_bytes(12)} B "
          f"({raw_payload_bytes(60) / cluster_payload_bytes(12):.1f}x smaller)")
    recovered = recover_cluster_window(cs, key, window.shape[0])
    err = float(jnp.mean(jnp.abs(recovered - window)) / jnp.std(window))
    print(f"host recovery (2r-approx): rel err = {err:.3f}")

    # --- importance-sampling coreset (paper D4) -----------------------------
    sc = importance_coreset(window, m=20, key=key)
    print(f"sampling coreset: {sc.indices.shape[0]} points, payload = "
          f"{sampling_payload_bytes(20, channels=3)} B")

    # --- memoization (paper D0) ---------------------------------------------
    memo = memo_decision(window, class_signatures(), threshold=0.95)
    print(f"memoization: hit={bool(memo.hit)} label={int(memo.label)} "
          f"corr={float(memo.max_corr):.3f}")

    # --- the kernel ops (Pallas on a TPU, the jnp oracle elsewhere) --------
    pts = jnp.stack([jnp.linspace(0, 1, 60)[:, None] * 4.0,
                     window[:, :1]], axis=-1).reshape(1, 60, 2)
    centers, radii, counts = kmeans_coreset_op(pts, k=12)
    corr = signature_corr_op(window[None], class_signatures())
    print(f"pallas kmeans engine: {centers.shape}; corr engine: {corr.shape}")

    # --- energy-aware decision flow over an RF harvest trace ---------------
    harvest = harvest_trace(key, 20, "rf")
    stored = jnp.asarray(30.0)
    pred = predictor_init()
    print("\nslot harvest stored decision (0=memo 2=qDNN 3=cluster 4=sample 5=defer)")
    for t in range(10):
        pred = predictor_update(pred, harvest[t])
        out = choose_decision(memo.max_corr * 0.5, stored,
                              predictor_forecast(pred), TABLE2_COSTS)
        stored = supercap_step(stored, harvest[t], out.spend)
        print(f"{t:4d} {float(harvest[t]):7.1f} {float(stored):6.1f}   "
              f"D{int(out.decision)}")


if __name__ == "__main__":
    main()
