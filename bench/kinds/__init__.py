"""Cell drivers, one per traffic ``kind``. Only these modules import the
program under test."""
