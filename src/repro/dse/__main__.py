from repro.dse import main
from repro.runtime.compile_cache import use_compile_cache

if __name__ == "__main__":
    use_compile_cache()
    raise SystemExit(main())
