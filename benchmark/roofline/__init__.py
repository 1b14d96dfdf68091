"""Operations and bytes of the program's Mosaic kernels, by kernel name:
what ``<kernel>_roofline`` divides by the device's peaks."""
