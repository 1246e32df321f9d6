"""Quickstart: the paper's headline result and what it buys an SSD.

    PYTHONPATH=src python examples/quickstart.py
"""

from repro.api import SSDConfig, steady_bandwidth_mb_s
from repro.core import timing
from repro.core.interface import InterfaceKind
from repro.core.nand import CellType


def main():
    # the paper's headline: CONV 50 MHz vs PROPOSED 83 MHz DDR ...
    clocks = timing.derive_paper_clocks()
    print(f"CONV     t_P,min = {clocks.conv_t_p_ns:.2f} ns -> {clocks.conv_mhz:.0f} MHz SDR")
    print(f"PROPOSED t_P,min = {clocks.prop_t_p_ns:.2f} ns -> {clocks.prop_mhz:.0f} MHz DDR")

    # ... and what it buys at SSD level (16-way SLC, paper Table 3)
    for kind in InterfaceKind:
        cfg = SSDConfig(interface=kind, cell=CellType.SLC, ways=16)
        print(f"  {kind.value:10s} 16-way SLC read : "
              f"{steady_bandwidth_mb_s(cfg, 'read'):7.1f} MB/s")


if __name__ == "__main__":
    main()
