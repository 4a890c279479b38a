"""One driver per traffic ``kind``; a new kind is a new file here."""
