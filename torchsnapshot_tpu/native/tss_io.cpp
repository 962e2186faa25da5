// Native I/O engine: O_DIRECT file read/write with buffered fallback.
//
// Rationale (TPU-VM analogue of the reference's performance layer): the
// reference (pure Python) relies on the OS page cache for write throughput
// (torchsnapshot/storage_plugins/fs.py:19-54 via aiofiles). Checkpoint
// payloads go through this engine instead: aligned O_DIRECT transfers through
// a bounce buffer, falling back to buffered I/O wherever O_DIRECT is
// unsupported (tmpfs, overlayfs, unaligned tails). The rates on record are
// those of the chip machine's 9p mount (PERF.md; it has no block device): a
// native write 2.5 GB/s an object through a bounce buffer the engine kept
// warm (0.6-0.9 while every object first touched one of its own: PERF.md
// section 6, PR 43), two at a time, timed around the whole call (the copy
// into the bounce buffer, the pwrite and the crc in series on one thread:
// the stamps below tell them apart); a native read 0.5-0.6
// GB/s as one serial stream, 3.4-3.9 GB/s as eight 4 MiB chunk reads in
// flight, 1.2-1.4 GB/s buffered or landing in a fresh destination with no
// bounce buffer (probe of PR 29).
//
// What is stamped where (all on CLOCK_MONOTONIC, Python's time.monotonic(),
// on the thread that does the work, GIL-free, and only when the caller hands
// in a stamps-out): a write stamps each chunk's copy into the bounce buffer,
// its pwrite and its crc (WriteStamps); a read stamps each chunk's whole
// interval, which is the pread AND the copy out of the bounce buffer into
// the destination's pages, and the pread(s) inside it (ReadPool::read_chunk).
//
// Writes are one serial loop an object; the caller (fs.py) caps how many
// run at once. Reads are chunk reads on a process-wide pool of reader
// threads (ReadPool below): the cap counts chunks on the mount, whichever
// objects they belong to, and lives here, not in the caller's interpreter.
//
// C ABI only — loaded from Python via ctypes (which releases the GIL for the
// duration of each call, so copies and syscalls overlap the event loop).
//
// All functions return 0 on success or -errno on failure.

#include <algorithm>
#include <atomic>
#include <cerrno>
#include <condition_variable>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <ctime>
#include <deque>
#include <mutex>
#include <thread>
#include <utility>
#include <vector>

#include <fcntl.h>
#include <pthread.h>
#include <sys/stat.h>
#include <unistd.h>

#include <zlib.h>  // crc32 for the inline digest path

namespace {

constexpr uint64_t kAlign = 4096;  // covers 512/4096 logical sector sizes
// The most bounce memory either side keeps: the reader threads' buffers
// together, and the write side's kept ones together.
constexpr uint64_t kMaxBounceBytes = 256ull << 20;

uint64_t align_up(uint64_t v) { return (v + kAlign - 1) / kAlign * kAlign; }
uint64_t align_down(uint64_t v) { return v / kAlign * kAlign; }

double monotonic_s() {
  struct timespec ts;
  clock_gettime(CLOCK_MONOTONIC, &ts);
  return static_cast<double>(ts.tv_sec) + 1e-9 * static_cast<double>(ts.tv_nsec);
}

// What the writing thread did with each chunk, when the caller asks: seven
// doubles a chunk. [0] to [1] it copied into the bounce buffer (borrowing the
// buffer on the first chunk, the memcpy, the tail's memset), [1] to [2] it
// was in pwrite (retries included), [2] to [3] it hashed (nothing where the
// caller wants no digest); [4] is the bytes of the object the pwrite took,
// and of those [5] had been copied into pages of the buffer that a copy had
// written before (warm) and [6] into pages no copy had (fresh: the copy was
// their first touch). A buffered write has no copy: [0] == [1], [5] == [6]
// == 0.
constexpr uint64_t kWriteStampDoubles = 7;

struct WriteStamps {
  std::vector<double> v;
  void add(double copy0, double mount0, double crc0, double end, uint64_t nbytes,
           uint64_t warm = 0, uint64_t fresh = 0) {
    const double row[kWriteStampDoubles] = {
        copy0, mount0, crc0, end, static_cast<double>(nbytes),
        static_cast<double>(warm), static_cast<double>(fresh)};
    v.insert(v.end(), row, row + kWriteStampDoubles);
  }
};

// The clock, where stamps are wanted: an unstamped call reads none.
double now_if(const void* wanted) { return wanted != nullptr ? monotonic_s() : 0.0; }

// Running CRC32 updated as write chunks advance (bytes hashed exactly once,
// in file order, while the chunk is cache-hot from the bounce copy).
// Deliberately crc-only: an embedded scalar SHA-256 was tried and measured
// ~5-10x slower than Python hashlib's OpenSSL (SHA-NI) path, so
// collision-resistant dedup digests stay in Python where the hardware
// implementation lives.
struct HashCtx {
  uLong crc = crc32(0L, Z_NULL, 0);

  void update(const char* p, uint64_t n) {
    const Bytef* b = reinterpret_cast<const Bytef*>(p);
    uint64_t done = 0;
    while (done < n) {  // zlib's crc32 takes uInt lengths
      uInt step = static_cast<uInt>(std::min<uint64_t>(n - done, 1u << 30));
      crc = crc32(crc, b + done, step);
      done += step;
    }
  }
};

// Buffered positional write of [src, src+nbytes) at file offset `off`; each
// pwrite is one chunk of `st`.
int write_buffered(int fd, const char* src, uint64_t nbytes, uint64_t off,
                   HashCtx* hc, WriteStamps* st) {
  uint64_t done = 0;
  while (done < nbytes) {
    size_t n = std::min<uint64_t>(nbytes - done, 1ull << 30);
    const double t0 = now_if(st);
    ssize_t w;
    do {
      w = pwrite(fd, src + done, n, off + done);
    } while (w < 0 && errno == EINTR);
    if (w < 0) return -errno;
    const double t1 = now_if(st);
    if (hc) hc->update(src + done, static_cast<uint64_t>(w));
    if (st) st->add(t0, t0, t1, hc ? monotonic_s() : t1, static_cast<uint64_t>(w));
    done += static_cast<uint64_t>(w);
  }
  return 0;
}

int read_buffered(int fd, char* dst, uint64_t nbytes, uint64_t off) {
  uint64_t done = 0;
  while (done < nbytes) {
    size_t n = std::min<uint64_t>(nbytes - done, 1ull << 30);
    ssize_t r = pread(fd, dst + done, n, off + done);
    if (r < 0) {
      if (errno == EINTR) continue;
      return -errno;
    }
    if (r == 0) return -EIO;  // unexpected EOF: caller sized the read
    done += static_cast<uint64_t>(r);
  }
  return 0;
}

// The bounce buffers of direct writes, lent and taken back. A write borrows
// one at its first direct chunk and returns it when its chunks are done,
// however they ended, so its copy lands in pages an earlier write has touched:
// a first touch is what a writer spent two thirds of its seconds on while
// every object had a buffer of its own (PERF.md section 6, PR 43). A borrow
// allocates only where nothing is kept or the one kept is too small, so the
// buffers in being are never more than the writes that were in flight at
// once (the caller's cap: fs.py's semaphore), and those kept never more than
// kMaxBounceBytes: one returned beyond that is freed. A kept buffer holds an
// earlier object's bytes; write_impl writes out only what it has just copied
// or zeroed.
struct Bounce {
  char* p = nullptr;
  uint64_t cap = 0;
  uint64_t touched = 0;  // how far into the buffer some copy has written
};

class BouncePool {
 public:
  // A buffer of `cap` bytes or more: the one returned last where it is large
  // enough (one that is not goes), else a new one. `p` null: no memory.
  Bounce borrow(uint64_t cap) {
    Bounce b;
    {
      std::lock_guard<std::mutex> g(mu_);
      if (!kept_.empty()) {
        b = kept_.back();
        kept_.pop_back();
        kept_bytes_ -= b.cap;
      }
      if (b.cap >= cap) {
        ++lent_;
        return b;
      }
    }
    free(b.p);
    void* p = nullptr;
    if (posix_memalign(&p, kAlign, cap) != 0) return Bounce{};
    std::lock_guard<std::mutex> g(mu_);
    ++lent_;
    ++allocated_;
    return Bounce{static_cast<char*>(p), cap, 0};
  }

  void give_back(const Bounce& b) {
    {
      std::lock_guard<std::mutex> g(mu_);
      --lent_;
      if (kept_bytes_ + b.cap <= kMaxBounceBytes) {
        kept_.push_back(b);
        kept_bytes_ += b.cap;
        return;
      }
    }
    free(b.p);
  }

  void stats(uint64_t out[4]) {
    std::lock_guard<std::mutex> g(mu_);
    out[0] = allocated_;
    out[1] = lent_;
    out[2] = kept_.size();
    out[3] = kept_bytes_;
  }

  void lock_for_fork() { mu_.lock(); }
  void unlock_in_parent() { mu_.unlock(); }
  // A forked child has none of the parent's writes, and a copy into a kept
  // buffer would fault every page in anew (copy on write): it starts empty.
  void empty_in_child() {
    for (const Bounce& b : kept_) free(b.p);
    kept_.clear();
    kept_bytes_ = lent_ = allocated_ = 0;
    mu_.unlock();
  }

 private:
  std::mutex mu_;
  std::vector<Bounce> kept_;
  uint64_t kept_bytes_ = 0, lent_ = 0, allocated_ = 0;
};

// Never destroyed: a writer thread may outlive main().
BouncePool* g_bounce = nullptr;

BouncePool& bounce_pool() {
  static BouncePool* const pool = [] {
    g_bounce = new BouncePool();
    pthread_atfork([] { g_bounce->lock_for_fork(); },
                   [] { g_bounce->unlock_in_parent(); },
                   [] { g_bounce->empty_in_child(); });
    return g_bounce;
  }();
  return *pool;
}

// Shared implementation of the write entry points; `hc` (nullable) receives
// a running crc32 over the bytes, updated chunk-by-chunk while the data is
// cache-hot from the bounce-buffer copy; `st` (nullable) what the thread did
// with each chunk. open, ftruncate and close are stamped by nobody: they are
// what is left of the caller's time around the call.
int write_impl(const char* path, const void* buf, uint64_t nbytes,
               int use_direct, uint64_t chunk_bytes, HashCtx* hc,
               WriteStamps* st) {
  const char* src = static_cast<const char*>(buf);
  const int base_flags = O_WRONLY | O_CREAT | O_TRUNC;

  int fd = -1;
  bool direct = use_direct != 0 && nbytes >= kAlign;
  if (direct) {
    fd = open(path, base_flags | O_DIRECT, 0644);
    if (fd < 0) direct = false;  // fs without O_DIRECT support
  }
  if (fd < 0) fd = open(path, base_flags, 0644);
  if (fd < 0) return -errno;

  int rc = 0;
  uint64_t off = 0;
  if (direct) {
    if (chunk_bytes < kAlign) chunk_bytes = 64ull << 20;
    chunk_bytes = align_down(chunk_bytes);
    // Borrowed at the first chunk, inside the copy's stamp (a new one's
    // pages are first touched by the copy below), and returned after the
    // last, whichever way the loop ends.
    Bounce bounce;
    while (off < nbytes) {
      uint64_t n = std::min(chunk_bytes, nbytes - off);
      uint64_t padded = align_up(n);
      const double copy0 = now_if(st);
      if (bounce.p == nullptr) {
        bounce = bounce_pool().borrow(chunk_bytes);
        if (bounce.p == nullptr) {
          close(fd);
          return -ENOMEM;
        }
      }
      memcpy(bounce.p, src + off, n);
      if (padded > n) memset(bounce.p + n, 0, padded - n);
      const uint64_t touched = bounce.touched;
      bounce.touched = std::max(touched, padded);
      const double mount0 = now_if(st);
      ssize_t w;
      do {
        w = pwrite(fd, bounce.p, padded, off);
      } while (w < 0 && errno == EINTR);
      const int err = errno;
      const double crc0 = now_if(st);
      // A short direct write only advances at an aligned boundary; a
      // sub-sector (or zero) count means this fs can't make progress under
      // O_DIRECT — finish buffered below rather than spinning.
      const uint64_t advanced =
          w < 0 ? 0 : std::min<uint64_t>(align_down(static_cast<uint64_t>(w)), n);
      if (hc && advanced > 0) hc->update(src + off, advanced);
      if (st) {
        const uint64_t warm = std::min(advanced, touched);
        st->add(copy0, mount0, crc0, hc && advanced > 0 ? monotonic_s() : crc0,
                advanced, warm, advanced - warm);
      }
      if (w < 0 && err != EINVAL) rc = -err;  // EINVAL: O_DIRECT rejected mid-stream
      if (advanced == 0) break;
      off += advanced;
    }
    if (bounce.p != nullptr) bounce_pool().give_back(bounce);
    if (rc == 0 && off < nbytes) {
      // Finish buffered (EINVAL fallback or zero-length write).
      int fd2 = open(path, O_WRONLY, 0644);
      if (fd2 < 0) {
        rc = -errno;
      } else {
        rc = write_buffered(fd2, src + off, nbytes - off, off, hc, st);
        if (close(fd2) < 0 && rc == 0) rc = -errno;
      }
    }
    // Drop the alignment padding from the final chunk.
    if (rc == 0 && ftruncate(fd, static_cast<off_t>(nbytes)) < 0) rc = -errno;
  } else {
    rc = write_buffered(fd, src, nbytes, 0, hc, st);
  }
  if (close(fd) < 0 && rc == 0) rc = -errno;
  return rc;
}

// Hand `st`'s rows to the caller as one malloc'd array (released with
// tss_free); nothing where the write failed or had no chunk.
int give_write_stamps(int rc, const WriteStamps& st, double** stamps_out,
                      uint64_t* chunks_out) {
  *stamps_out = nullptr;
  *chunks_out = 0;
  if (rc != 0 || st.v.empty()) return rc;
  double* out = static_cast<double*>(malloc(st.v.size() * sizeof(double)));
  if (out == nullptr) return -ENOMEM;
  std::copy(st.v.begin(), st.v.end(), out);
  *stamps_out = out;
  *chunks_out = st.v.size() / kWriteStampDoubles;
  return rc;
}

// ---------------------------------------------------------------- read side
//
// One object is read as positional chunk reads, and a process-wide pool of
// `depth` reader threads keeps that many chunk reads on the mount at once,
// whichever objects they belong to: the chunks of one large object, or whole
// small objects side by side. A thread is the token: the chunk at the head of
// the queue starts the moment any read finishes, with no trip through the
// caller's interpreter in between. Each thread keeps one aligned bounce buffer
// for its lifetime, so the pool holds at most depth x (chunk + one sector),
// and the chunk is clamped so that this stays under kMaxBounceBytes.

constexpr uint64_t kReadStampDoubles = 4;
// The depth a new pool starts with: what tss_read_pool_configure last set, so
// that a forked child's pool is sized as its parent's was.
std::atomic<unsigned> g_read_depth{8};

struct ReadJob {
  char* dst = nullptr;
  uint64_t offset = 0, nbytes = 0, chunk = 0, file_size = 0;
  int fd_direct = -1, fd_buffered = -1;
  double* stamps = nullptr;
  int64_t fail_chunk = -1;
  // Guarded by the pool's mutex.
  uint64_t pending = 0;
  int rc = 0;
  std::condition_variable done;
};

// The seconds one chunk spent in pread, when stamps are asked for: `first` is
// where its first pread began, `total` the sum over its preads (several only
// after a short read or EINTR).
struct PreadClock {
  double first = 0.0, total = 0.0;
  void add(double t0, double t1) {
    if (total == 0.0) first = t0;
    total += t1 - t0;
  }
};

// One chunk under O_DIRECT: [file_off, file_off + n) into `out` through the
// thread's bounce buffer. (Reading straight into an aligned destination was
// measured and is slower: pinning a fresh destination's pages for the
// transfer caps the mount at 1.3 GB/s whatever the depth, where the copy out
// of a warm bounce buffer faults them in on every reader thread at once:
// PERF.md section 6, PR 29.) Returns the bytes delivered (short of n: the
// mount made no progress under O_DIRECT or refused it, and the caller
// finishes buffered) or -errno.
int64_t read_chunk_direct(const ReadJob& job, char* bounce, char* out,
                          uint64_t file_off, uint64_t n, PreadClock* clock) {
  uint64_t done = 0;
  while (done < n) {
    const uint64_t want_off = file_off + done;
    const uint64_t read_off = align_down(want_off);
    const uint64_t lead = want_off - read_off;
    const uint64_t left = n - done;
    // O_DIRECT reads must not extend past EOF by more than a sector pad.
    const uint64_t padded =
        std::min(align_up(lead + left), align_up(job.file_size - read_off));
    const double t0 = now_if(clock);
    ssize_t r = pread(job.fd_direct, bounce, padded, read_off);
    const int err = errno;  // before the stamp's clock_gettime
    if (clock) clock->add(t0, monotonic_s());
    if (r < 0) {
      if (err == EINTR) continue;
      if (err == EINVAL) break;  // refused mid-stream: finish buffered
      return -err;
    }
    const uint64_t got = static_cast<uint64_t>(r);
    // No forward progress (a short read at an unaligned boundary, seen on
    // NFS/FUSE): finish buffered instead of failing the restore.
    if (got <= lead) break;
    const uint64_t usable = std::min(got - lead, left);
    memcpy(out + done, bounce + lead, usable);
    done += usable;
  }
  return static_cast<int64_t>(done);
}

class ReadPool {
 public:
  unsigned depth() {
    std::lock_guard<std::mutex> g(mu_);
    return depth_;
  }

  // Queue every chunk of `job` and wait until the last has landed.
  int run(ReadJob* job, uint64_t chunks) {
    std::unique_lock<std::mutex> lk(mu_);
    if (threads_.empty() && !stop_) spawn_locked();
    job->pending = chunks;
    for (uint64_t k = 0; k < chunks; ++k) queue_.emplace_back(job, k);
    wake_.notify_all();
    job->done.wait(lk, [job] { return job->pending == 0; });
    return job->rc;
  }

  void configure(unsigned depth) {
    std::vector<std::thread> old;
    {
      std::lock_guard<std::mutex> g(mu_);
      if (depth == depth_ && !threads_.empty()) return;
      stop_ = true;
      old.swap(threads_);
    }
    wake_.notify_all();
    for (auto& t : old) t.join();
    std::lock_guard<std::mutex> g(mu_);
    stop_ = false;
    depth_ = depth;
    g_read_depth.store(depth);
    high_water_ = 0;  // every reader has been joined: nothing is in flight
    chunks_read_ = 0;
    spawn_locked();
  }

  void stats(uint64_t out[6]) {
    std::lock_guard<std::mutex> g(mu_);
    out[0] = depth_;
    out[1] = in_flight_;
    out[2] = high_water_;
    out[3] = buffers_;
    out[4] = buffer_bytes_;
    out[5] = chunks_read_;
  }

 private:
  void spawn_locked() {
    for (unsigned i = 0; i < depth_; ++i) threads_.emplace_back([this] { serve(); });
  }

  void serve() {
    pthread_setname_np(pthread_self(), "tss-read");
    char* bounce = nullptr;
    uint64_t bounce_cap = 0;
    std::unique_lock<std::mutex> lk(mu_);
    for (;;) {
      wake_.wait(lk, [this] { return stop_ || !queue_.empty(); });
      if (stop_) break;
      ReadJob* job = queue_.front().first;
      const uint64_t k = queue_.front().second;
      queue_.pop_front();
      int rc = 0;
      if (job->rc == 0) {  // a failed object's other chunks are not read
        high_water_ = std::max<uint64_t>(high_water_, ++in_flight_);
        if (job->fd_direct >= 0 && bounce_cap < job->chunk + kAlign) {
          buffer_bytes_ -= bounce_cap;
          buffers_ -= bounce != nullptr;
          free(bounce);
          bounce_cap = job->chunk + kAlign;
          void* p = nullptr;
          if (posix_memalign(&p, kAlign, bounce_cap) != 0) p = nullptr;
          bounce = static_cast<char*>(p);
          if (bounce == nullptr) bounce_cap = 0;
          buffer_bytes_ += bounce_cap;
          buffers_ += bounce != nullptr;
        }
        lk.unlock();
        rc = read_chunk(*job, k, bounce);
        lk.lock();
        --in_flight_;
        ++chunks_read_;
      }
      if (rc != 0 && job->rc == 0) job->rc = rc;
      if (--job->pending == 0) job->done.notify_one();
    }
    buffer_bytes_ -= bounce_cap;
    buffers_ -= bounce != nullptr;
    free(bounce);
  }

  // Four doubles a chunk in `job.stamps`: [0] to [1] the chunk's whole
  // interval on its reader thread, the pread into the warm bounce buffer and
  // the copy out of it into the destination's (fresh) pages; [2] to [3] the
  // pread inside it (a buffered tail, which reads straight into the
  // destination, counts as pread), so the rest of the chunk is the copy.
  // Several preads of one chunk are laid end to end from the first one's
  // start: their sum is exact, their place in the chunk is not.
  static int read_chunk(const ReadJob& job, uint64_t k, char* bounce) {
    const uint64_t at = k * job.chunk;
    const uint64_t n = std::min(job.chunk, job.nbytes - at);
    char* out = job.dst + at;
    const uint64_t file_off = job.offset + at;
    PreadClock preads;
    PreadClock* clock = job.stamps != nullptr ? &preads : nullptr;
    const double t0 = now_if(clock);
    int rc = 0;
    if (static_cast<int64_t>(k) == job.fail_chunk) {
      rc = -ESTALE;
    } else {
      uint64_t done = 0;
      if (job.fd_direct >= 0 && bounce != nullptr) {
        int64_t got = read_chunk_direct(job, bounce, out, file_off, n, clock);
        if (got < 0) rc = static_cast<int>(got);
        else done = static_cast<uint64_t>(got);
      }
      if (rc == 0 && done < n) {
        const double b0 = now_if(clock);
        rc = read_buffered(job.fd_buffered, out + done, n - done, file_off + done);
        if (clock) clock->add(b0, monotonic_s());
      }
    }
    if (clock) {
      double* row = job.stamps + kReadStampDoubles * k;
      row[0] = t0;
      row[1] = monotonic_s();
      row[2] = preads.total > 0.0 ? preads.first : t0;
      row[3] = row[2] + preads.total;
    }
    return rc;
  }

  std::mutex mu_;
  std::condition_variable wake_;
  std::deque<std::pair<ReadJob*, uint64_t>> queue_;
  std::vector<std::thread> threads_;
  unsigned depth_ = g_read_depth.load();
  bool stop_ = false;
  uint64_t in_flight_ = 0, high_water_ = 0, buffers_ = 0, buffer_bytes_ = 0,
           chunks_read_ = 0;
};

// Never destroyed: its threads sleep on `wake_` until the process exits. A
// forked child has none of them, so it starts a pool of its own.
std::atomic<ReadPool*> g_pool{nullptr};
std::mutex g_pool_mu;

ReadPool& pool() {
  ReadPool* p = g_pool.load(std::memory_order_acquire);
  if (p == nullptr) {
    std::lock_guard<std::mutex> g(g_pool_mu);
    p = g_pool.load(std::memory_order_relaxed);
    if (p == nullptr) {
      static const int registered = pthread_atfork(
          [] { g_pool_mu.lock(); }, [] { g_pool_mu.unlock(); },
          [] {
            g_pool.store(nullptr, std::memory_order_relaxed);
            g_pool_mu.unlock();
          });
      (void)registered;
      p = new ReadPool();
      g_pool.store(p, std::memory_order_release);
    }
  }
  return *p;
}

}  // namespace

extern "C" {

int tss_io_version() { return 7; }

// Create/truncate `path` and write `nbytes` from `buf`.
// use_direct != 0 attempts O_DIRECT via an aligned bounce buffer of
// chunk_bytes; any O_DIRECT failure falls back to buffered I/O and the write
// still succeeds. `stamps_out`, when given, receives an array of seven
// doubles a chunk (`*chunks_out` chunks; the caller releases it with
// tss_free): what the writing thread did with the chunk and when, on
// CLOCK_MONOTONIC (WriteStamps above). Null: the call reads no clock.
int tss_write_file(const char* path, const void* buf, uint64_t nbytes,
                   int use_direct, uint64_t chunk_bytes, double** stamps_out,
                   uint64_t* chunks_out) {
  WriteStamps st;
  int rc = write_impl(path, buf, nbytes, use_direct, chunk_bytes, nullptr,
                      stamps_out != nullptr ? &st : nullptr);
  if (stamps_out != nullptr) rc = give_write_stamps(rc, st, stamps_out, chunks_out);
  return rc;
}

// Like tss_write_file, but also computes the zlib crc32 over the written
// bytes in the same pass (*crc_out): the separate memory sweep the Python
// hashing path pays per object is folded into the write loop here.
int tss_write_file_digest(const char* path, const void* buf, uint64_t nbytes,
                          int use_direct, uint64_t chunk_bytes,
                          uint32_t* crc_out, double** stamps_out,
                          uint64_t* chunks_out) {
  HashCtx hc;
  WriteStamps st;
  int rc = write_impl(path, buf, nbytes, use_direct, chunk_bytes, &hc,
                      stamps_out != nullptr ? &st : nullptr);
  if (rc == 0 && crc_out) *crc_out = static_cast<uint32_t>(hc.crc);
  if (stamps_out != nullptr) rc = give_write_stamps(rc, st, stamps_out, chunks_out);
  return rc;
}

// Read `nbytes` at byte `offset` of `path` into `dst` as chunk reads of
// `chunk_bytes` on the reader pool (see ReadPool above): the call returns when
// every chunk has landed. Fails with -EIO if the file is shorter than
// offset+nbytes (callers size reads from the manifest). `stamps_out`, when
// given, receives an array of four doubles a chunk (`*chunks_out` chunks; the
// caller releases it with tss_free): each chunk's interval on its reader
// thread and the pread inside it, on CLOCK_MONOTONIC (Python's
// time.monotonic(); ReadPool::read_chunk). `fail_chunk` >= 0
// is the fault harness's torn read: that chunk fails with -ESTALE, the
// others land.
int tss_read_file(const char* path, void* dst, uint64_t offset, uint64_t nbytes,
                  int use_direct, uint64_t chunk_bytes, double** stamps_out,
                  uint64_t* chunks_out, int64_t fail_chunk) {
  ReadJob job;
  job.dst = static_cast<char*>(dst);
  job.offset = offset;
  job.nbytes = nbytes;
  job.fail_chunk = fail_chunk;
  if (stamps_out != nullptr) {
    *stamps_out = nullptr;
    *chunks_out = 0;
  }

  if (use_direct != 0 && nbytes >= kAlign) {
    job.fd_direct = open(path, O_RDONLY | O_DIRECT);  // < 0: fs without it
  }
  job.fd_buffered = open(path, O_RDONLY);
  if (job.fd_buffered < 0) {
    int rc = -errno;
    if (job.fd_direct >= 0) close(job.fd_direct);
    return rc;
  }
  int rc = 0;
  struct stat st;
  if (fstat(job.fd_buffered, &st) < 0) {
    rc = -errno;
  } else {
    job.file_size = static_cast<uint64_t>(st.st_size);
    if (offset + nbytes > job.file_size) rc = -EIO;
  }
  if (rc == 0 && nbytes > 0) {
    const uint64_t cap = kMaxBounceBytes / pool().depth();
    if (chunk_bytes < kAlign) chunk_bytes = cap;
    job.chunk = std::max(kAlign, align_down(std::min(chunk_bytes, cap)));
    const uint64_t chunks = (nbytes + job.chunk - 1) / job.chunk;
    if (stamps_out != nullptr) {
      job.stamps = static_cast<double*>(calloc(kReadStampDoubles * chunks, sizeof(double)));
      if (job.stamps == nullptr) rc = -ENOMEM;
    }
    if (rc == 0) rc = pool().run(&job, chunks);
    if (rc == 0 && stamps_out != nullptr) {
      *stamps_out = job.stamps;
      *chunks_out = chunks;
    } else {
      free(job.stamps);
    }
  }
  if (job.fd_direct >= 0) close(job.fd_direct);
  if (close(job.fd_buffered) < 0 && rc == 0) rc = -errno;
  return rc;
}

// Set the number of chunk reads the pool keeps on the mount at once (and so
// its reader threads and bounce buffers). Chunks already queued are read by
// the new threads.
int tss_read_pool_configure(int depth) {
  if (depth < 1) return -EINVAL;
  pool().configure(static_cast<unsigned>(depth));
  return 0;
}

void tss_free(void* p) { free(p); }

// Gauges of the write side's bounce buffers: allocated since the process (or
// its fork) began, lent to a write now, kept for the next one, the bytes of
// those kept.
void tss_write_bounce_stats(uint64_t out[4]) { bounce_pool().stats(out); }

// Gauges of the reader pool: depth, chunk reads in flight now, the most ever
// in flight since the last configure, bounce buffers held, their bytes,
// chunks read since the last configure.
void tss_read_pool_stats(uint64_t out[6]) { pool().stats(out); }

// What the touchers of one stretch of memory share (native.TouchState): the
// owner raises `wanted` and sets `stop`; the touchers advance `claimed` as
// they take stripes and `done` as they finish them.
struct TouchState {
  uint64_t claimed;
  uint64_t wanted;
  uint64_t done;
  int32_t stop;
};

// One toucher of host_arena.py: first-touch `base[0, st->wanted)`, a byte a
// page of `page_bytes`, in stripes of `stripe_bytes` claimed from
// `st->claimed` upward (so the stripes go out in address order), sleeping
// where nothing more is wanted yet, until `st->stop` reads non-zero: looked at
// before every page, so a toucher is out of the memory a page fault after it
// is told. `*unfinished_at` receives the offset of the first page of its last
// stripe that it did not touch (UINT64_MAX: it finished every stripe it
// claimed). The caller owns the memory and nobody reads it yet (no view of it
// is out while a toucher runs), so what is written is of no account. The
// whole life of the thread is this one call: it never takes the GIL.
void tss_touch_stripes(void* base, TouchState* st, uint64_t stripe_bytes,
                       uint64_t page_bytes, uint64_t* unfinished_at) {
  volatile char* p = static_cast<volatile char*>(base);
  *unfinished_at = UINT64_MAX;
  const struct timespec nap = {0, 50 * 1000};
  while (__atomic_load_n(&st->stop, __ATOMIC_ACQUIRE) == 0) {
    uint64_t from = __atomic_load_n(&st->claimed, __ATOMIC_RELAXED);
    const uint64_t want = __atomic_load_n(&st->wanted, __ATOMIC_ACQUIRE);
    if (from >= want) {
      nanosleep(&nap, nullptr);
      continue;
    }
    const uint64_t to = std::min(from + stripe_bytes, want);
    if (!__atomic_compare_exchange_n(&st->claimed, &from, to, false,
                                     __ATOMIC_ACQ_REL, __ATOMIC_RELAXED)) {
      continue;
    }
    for (uint64_t at = from; at < to; at += page_bytes) {
      if (__atomic_load_n(&st->stop, __ATOMIC_RELAXED) != 0) {
        *unfinished_at = at;
        return;
      }
      p[at] = 0;
    }
    __atomic_fetch_add(&st->done, to - from, __ATOMIC_RELEASE);
  }
}

// File size probe (0 on success with *size set).
int tss_file_size(const char* path, uint64_t* size) {
  struct stat st;
  if (stat(path, &st) < 0) return -errno;
  *size = static_cast<uint64_t>(st.st_size);
  return 0;
}

}  // extern "C"
