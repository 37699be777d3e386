"""Operations and bytes of the work each cell *needs*, from its sizes."""
