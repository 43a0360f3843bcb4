"""Traffic generators; each mix ``<name>.json`` names one by ``kind``."""
