"""Traffic mixes, one data file each, and the one generator that reads them."""
