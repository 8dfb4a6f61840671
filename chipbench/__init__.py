"""chipbench: the chip benchmark's yardstick. See README.md beside this file."""
