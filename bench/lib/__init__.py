"""The benchmark's yardstick; no module here is edited by a later cell."""
