"""Optimal transmit powers (Theorem 1 + Corollaries 1-2) walkthrough.

    PYTHONPATH=src python examples/power_allocation.py

Shows how expected leakage E[I] (Eq. 30) moves with trainer/decoy power,
and that the closed-form powers hit the constrained optimum.
"""
import jax.numpy as jnp
import numpy as np

from repro.core.channel import NetworkConfig, data_rate, tx_time
from repro.core.leakage import (
    capture_probability,
    expected_leakage,
    optimal_powers_single_decoy,
    optimal_powers_single_eave,
)


def main():
    net = NetworkConfig()
    d_tx_rx = jnp.asarray(150.0)  # trainer -> receiver
    d_tx_d = jnp.asarray(180.0)  # decoy interference at the receiver
    dist_e = jnp.asarray([250.0])  # trainer -> eavesdropper
    dd_e = jnp.asarray([[90.0]])  # decoy -> eavesdropper (close!)
    q = jnp.asarray([net.monitor_prob])
    bits = jnp.asarray(2e6)
    b_t, b_e = jnp.asarray(1.5), jnp.asarray(3.0)

    print("E[leak] vs trainer power (decoy fixed 0.5 W):")
    for ps in [0.05, 0.2, 0.5, 1.0, 1.5]:
        leak = float(expected_leakage(jnp.asarray(ps), dist_e, jnp.asarray([0.5]),
                                      dd_e, q, jnp.asarray(1.0)))
        rate = float(data_rate(jnp.asarray(ps), d_tx_rx, jnp.asarray([0.5]),
                               jnp.asarray([d_tx_d]), net))
        print(f"  p_s={ps:4.2f} W  E[I]={leak:.4f}  hop_time={float(tx_time(bits, rate)):6.2f} s")

    print("\nE[leak] vs decoy power (trainer fixed 0.5 W):")
    for pd in [0.0, 0.1, 0.5, 1.0, 2.0]:
        leak = float(expected_leakage(jnp.asarray(0.5), dist_e, jnp.asarray([pd]),
                                      dd_e, q, jnp.asarray(1.0)))
        print(f"  p_d={pd:4.2f} W  E[I]={leak:.4f}")

    p_s, p_d = optimal_powers_single_decoy(bits, d_tx_rx, d_tx_d, b_t, b_e, net)
    leak = float(expected_leakage(p_s, dist_e, jnp.asarray([p_d]), dd_e, q, jnp.asarray(1.0)))
    rate = data_rate(p_s, d_tx_rx, jnp.asarray([p_d]), jnp.asarray([d_tx_d]), net)
    print(f"\nCorollary 1 (|D|=1): p_s*={float(p_s):.3f} W  p_d*={float(p_d):.3f} W")
    print(f"  E[I]={leak:.4f}, hop_time={float(tx_time(bits, rate)):.3f} s (= B_T), "
          f"energy={(float(p_s)+float(p_d))*float(b_t):.3f} J (= B_E)")

    dd_many = jnp.asarray([100.0, 250.0, 400.0])
    p_s2, p_d2 = optimal_powers_single_eave(bits, d_tx_rx, dd_many, b_t, b_e, net)
    print(f"\nCorollary 2 (|E|=1, 3 decoys): p_s*={float(p_s2):.3f} W")
    for i, pd in enumerate(np.asarray(p_d2)):
        print(f"  decoy {i}: d_e={float(dd_many[i]):.0f} m  p_d*={pd:.3f} W "
              f"(received at eave: {pd/float(dd_many[i])**2:.2e})")
    print("  -> received decoy powers are water-levelled at the eavesdropper.")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    main()
