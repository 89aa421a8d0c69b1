"""On-chip benchmark of the FlashOmni serving path (see ``chipbench.run``)."""
